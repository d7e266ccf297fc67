"""Shared backend/runtime policy for the Pallas kernel packages.

Every kernel entry point — the low-level ``*_pallas`` functions in
``kernel.py`` as well as the public wrappers in ``ops.py`` — resolves its
``interpret=`` default through :func:`default_interpret`, so there is
exactly ONE place that decides "compile natively on TPU, emulate
elsewhere".  (Previously the low-level entry points hard-defaulted to
``interpret=True`` even on TPU when called directly, silently running the
Python emulation on hardware that could compile the kernel.)

The block width and tile-size defaults (``default_w`` for W,
``default_tb`` for the sample/row axis, ``default_tk`` for the category
axis) live here too (DESIGN.md §3): ``repro.sampling.plan`` launches
every kernel with them.  They stay importable without pulling in jax at
module import time.
"""

from __future__ import annotations

import math
from typing import Optional


def default_interpret(backend: Optional[str] = None) -> bool:
    """True when Pallas must run in interpret mode (non-TPU backends).

    ``backend`` overrides the detected JAX default backend (tests inject
    "tpu"/"cpu" here; production callers pass nothing).
    """
    if backend is None:
        import jax

        backend = jax.default_backend()
    return backend != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The single policy behind every kernel's ``interpret=None`` default."""
    if interpret is None:
        return default_interpret()
    return bool(interpret)


def default_w(K: int) -> int:
    """W ~ sqrt(K) (minimizes K/W + W), rounded to a power of two in
    [8, 128] — 128 at vocabulary scale."""
    if K <= 64:
        return 8
    w = 2 ** int(round(math.log2(math.sqrt(K))))
    return max(8, min(128, w))


def default_tb(B: int) -> int:
    """Row-tile (samples per grid step) for the tiled draw kernels.

    8 is the fp32 sublane count — the smallest tile the VPU fills — and
    divides every batch the padding path produces; larger batches amortize
    grid overhead better with 16.
    """
    return 8 if B < 1024 else 16


def row_tile(tb: int) -> int:
    """A row tile the TPU can block: a multiple of the 8 fp32 sublanes."""
    return max(8, -(-tb // 8) * 8)


def default_tk(K: int, W: int) -> int:
    """Category-tile for pass A: a multiple of W near 512 lanes, clamped
    to the padded row length so tiny K never over-pads."""
    Kp = -(-K // W) * W
    tk = max(W, (512 // W) * W)
    return min(tk, Kp)
