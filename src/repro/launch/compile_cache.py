"""JAX's persistent compilation cache, for command-line entry points.

A cold run recompiles every Pallas kernel and a whole 36-layer decode
step.  The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train`` and the ``benchmarks`` runners) call
:func:`enable` before their first compile, so later processes on the same
machine load those programs instead.  Importing a library module never
turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed: a cache directory that moved between runs would never be hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> Optional[str]:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it as
    its own setting, and it is left alone.  Otherwise an accelerator's
    programs go to ``.jax_cache`` in the checkout's root; on the CPU the
    cache stays off (None), since XLA:CPU warns that a reloaded program
    may not match the host's machine features."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
