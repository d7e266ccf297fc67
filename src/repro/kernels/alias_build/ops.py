"""Public wrappers for on-device alias table construction.

Two implementations of the same split-based (PSA) build live behind
:func:`build_alias_tables_device`:

* ``impl="pallas"`` — the tiled assembly kernel in :mod:`kernel`
  (compiled natively on TPU; interpret-mode emulation elsewhere), and
* ``impl="xla"``   — a pure-XLA twin running the *identical* shared
  ``_assemble`` math on full rows (``jnp.take_along_axis`` instead of
  one-hot lane buckets).

``impl=None`` picks Pallas on TPU and the XLA twin elsewhere, mirroring
the ``interpret`` policy in :mod:`repro.kernels.runtime` — the same
dual structure as :mod:`repro.kernels.lda_draw`.

Either way the build is a closed jaxpr built from cumsums, gathers and
fixed-trip binary searches — **no sort anywhere**: the stable partition
is a cumsum-indexed permutation (both directions closed-form), and the
merged sweep rank exploits that both split keys are monotone (see
``kernel.py``), so merging them is one batched bisection, not a
lexsort.  That matters beyond elegance: XLA's CPU sort is a scalar
comparator loop ~25x slower than its gathers, so a sort-based build
loses to the numpy host builder — this formulation beats it (the
``strategy_zoo`` bench rows track the ratio).  No host callback, no
``lax.while_loop``, no data-dependent trip counts — so
``Categorical.refreshed`` and the sparse-LDA training sweep can rebuild
alias tables *inside* a jitted step (the jaxpr gate in
``tests/test_alias_forest.py`` pins no-while/no-callback/no-sort).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import runtime
from repro.kernels.alias_build.kernel import (
    _assemble,
    _light_prefix,
    alias_assemble_pallas,
)


def _resolve_impl(impl: Optional[str]) -> str:
    if impl is None:
        return "xla" if runtime.default_interpret() else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be 'pallas' or 'xla', got {impl!r}")
    return impl


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _partition(weights: jnp.ndarray):
    """Scale to mean 1 and stable-partition each row into lights
    (s <= 1, index order) then heavies (s > 1, index order).

    No sort: the orig -> sorted-position map ``inv`` is closed-form from
    the inclusive class counts (cumsums), and ``order`` is its inverse —
    one flat scatter of iota (a permutation, so indices are unique).

    Zero-total rows scale to all-ones (every bucket keeps prob 1 — the
    draw degrades to uniform, matching the host builder's ``ok`` mask).
    Returns ``(s_sorted, order, inv, nL)`` with ``order`` mapping sorted
    position -> original index and ``inv`` its inverse."""
    w = weights.astype(jnp.float32)
    B, K = w.shape
    tot = jnp.sum(w, axis=-1, keepdims=True)
    ok = tot > 0
    s = jnp.where(ok, w * (K / jnp.where(ok, tot, 1.0)), 1.0)
    heavy = s > 1.0
    cH = jnp.cumsum(heavy, axis=-1).astype(jnp.int32)      # inclusive
    iota1 = jnp.arange(1, K + 1, dtype=jnp.int32)[None, :]
    cL = iota1 - cH
    nL = cL[:, -1]
    inv = jnp.where(heavy, nL[:, None] + cH - 1, cL - 1)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    iota = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[None, :], (B, K))
    order = (
        jnp.zeros((B * K,), jnp.int32)
        .at[(rows * K + inv).ravel()]
        .set(iota.ravel(), unique_indices=True)
        .reshape(B, K)
    )
    s_sorted = jnp.take_along_axis(s, order, axis=-1)
    return s_sorted, order, inv, nL


def _merged_rank(
    s_sorted: jnp.ndarray, nL: jnp.ndarray, cs: jnp.ndarray
) -> jnp.ndarray:
    """Each position's rank in the merged sweep order of the light keys
    ``b`` and heavy keys ``A`` (ties: A before b, then position — the
    order the sequential pack sweep visits them in).

    Both key sequences are monotone in position (b steps by ``1 - s >=
    0`` over lights, A by ``s - 1 >= 0`` over heavies), so no sort is
    needed: merging two sorted sequences is rank arithmetic —
    ``rank(light i) = i + #{A <= b_i}`` (ties count: A first) and
    ``rank(heavy j) = j + #{b < A_j}``.  Both counts come from ONE
    fixed-trip clamped bisection over the two +/-inf-masked halves laid
    side by side (lights query the A half with ``<=``, heavies the b
    half with ``<``) — ``take_along_axis`` gathers only: XLA CPU gathers
    are fast where its sorts and the stock ``jnp.searchsorted`` scan are
    not, and the fixed trip count keeps the jaxpr free of ``while``."""
    from repro.kernels.alias_build.kernel import _sweep_vals

    B, Kp = s_sorted.shape
    pos, light, _csL, b, A = _sweep_vals(s_sorted, nL, cs)
    nLcol = nL[:, None]
    A_asc = jnp.where(light, -jnp.inf, A)    # -inf prefix, then rising A
    b_asc = jnp.where(light, b, jnp.inf)     # rising b, then +inf tail
    halves = jnp.concatenate([A_asc, b_asc], axis=-1)      # (B, 2*Kp)
    q = jnp.where(light, b, A)
    base = jnp.where(light, 0, Kp)
    lo = base
    hi = base + Kp
    for _ in range(max(1, Kp.bit_length())):
        mid = jnp.minimum((lo + hi) >> 1, base + Kp - 1)
        am = jnp.take_along_axis(halves, mid, axis=-1)
        go = jnp.where(light, am <= q, am < q)
        open_ = lo < hi
        lo = jnp.where(open_ & go, mid + 1, lo)
        hi = jnp.where(open_ & ~go, mid, hi)
    cnt = lo - base
    rank = jnp.where(light, pos + (cnt - nLcol), (pos - nLcol) + cnt)
    return rank.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tb", "impl", "interpret"))
def build_alias_tables_device(
    weights,
    tb: int = 8,
    impl: Optional[str] = None,
    interpret: bool | None = None,
):
    """(B, K) (or (K,)) non-negative weights -> ``AliasTable`` with
    ``prob`` (B, K) float32 in [0, 1] and ``alias`` (B, K) int32 — built
    entirely on device (jit/shard_map composable, zero host round-trips).

    Draw semantics match the host builder in distribution (chi^2 parity):
    pick column k uniformly, accept k if ``u < prob[k]`` else take
    ``alias[k]``."""
    from repro.core.alias import AliasTable

    w = jnp.asarray(weights)
    squeeze = w.ndim == 1
    if squeeze:
        w = w[None, :]
    if w.ndim != 2:
        raise ValueError(f"expected (B, K) weights, got shape {w.shape}")
    B, K = w.shape
    s_sorted, order, inv, nL = _partition(w)

    if _resolve_impl(impl) == "pallas":
        Kp = _next_pow2(K)
        padB = (-B) % runtime.row_tile(tb)
        # pad with s = 1 pseudo-heavies: A stays constant on the pad tail
        # (ties resolve after every real entry), so real ranks are
        # untouched and pad outputs are sliced away below
        sp = jnp.pad(
            s_sorted, ((0, padB), (0, Kp - K)), constant_values=1.0
        )
        nLp = jnp.pad(nL, (0, padB), constant_values=Kp)
        cs = jnp.cumsum(sp, axis=-1)
        rank = _merged_rank(sp, nLp, cs)
        prob_s, apos = alias_assemble_pallas(
            sp, nLp, rank, cs, _light_prefix(cs, nLp, rank),
            tb=runtime.row_tile(tb), interpret=interpret,
        )
        prob_s, apos = prob_s[:B, :K], apos[:B, :K]
    else:
        cs = jnp.cumsum(s_sorted, axis=-1)
        rank = _merged_rank(s_sorted, nL, cs)
        prob_s, apos = _assemble(
            s_sorted, nL, rank, cs, _light_prefix(cs, nL, rank)
        )

    # position space -> original category ids, undoing the partition
    apos = jnp.minimum(apos, K - 1)
    alias_s = jnp.take_along_axis(order, apos, axis=-1)
    prob = jnp.take_along_axis(prob_s, inv, axis=-1)
    alias = jnp.take_along_axis(alias_s, inv, axis=-1).astype(jnp.int32)
    if squeeze:
        prob, alias = prob[0], alias[0]
    return AliasTable(prob=prob, alias=alias)
