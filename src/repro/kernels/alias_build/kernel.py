"""Tiled Pallas builder for on-device alias tables (PSA split assembly).

Lehmann/Hübschle-Schneider/Sanders ("Weighted Random Sampling on GPUs")
showed alias tables can be built *on device* by replacing Vose's two
sequential worklists with prefix-sum splits.  The key invariant (derived
in DESIGN.md §11): during the pack sweep every completed bucket holds
exactly weight 1, so when light ``i`` is assigned with ``j`` heavies
fully drained, the current heavy's residual is

    r = PL(i) + PH(j+1) - (i + j)        (weight conservation)

with PL/PH the light/heavy prefix sums over the partitioned order.  Both
split keys — ``A(j) = PH(j+1) - j`` (strictly increasing: heavy surplus
> 0) and ``b(i) = i - PL(i) + 1`` (non-decreasing: light deficit >= 0) —
are monotone, so the entire sweep collapses to *rank arithmetic* in their
merged order:

    heavy serving light i:        position  nL + (rank(b_i) - i)
    lights drained when j empties: count    rank(A_j) - j

The merged rank is two fixed-trip batched bisections (computed XLA-side,
like the partition — no sort anywhere, see :mod:`ops`);
this module's kernel is the tiled *assembly*: grid ``(Bp//tb,)``, each
step loads a (tb, Kp) tile of pow2-padded scaled weights plus its rank,
row-prefix and gathered light-prefix rows and emits (prob,
alias-position) with elementwise math and masked reductions (no
data-dependent loop anywhere).  The row prefix ``cs`` and the assembly's
one per-row gather, ``PL(i)``, run XLA-side with the rank
(:func:`_light_prefix`), so kernel and twin sum in the same order.

``_sweep_vals`` / ``_assemble`` are shared verbatim by the pure-XLA twin
in :mod:`ops` — the two implementations cannot drift.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import runtime


# ---------------------------------------------------------------------------
# Shared tile math (used by the Pallas kernel AND the XLA twin in ops.py)
# ---------------------------------------------------------------------------


def _sweep_vals(s_sorted: jnp.ndarray, nL: jnp.ndarray, cs: jnp.ndarray):
    """Per-position sweep quantities from lights-then-heavies scaled
    weights and their inclusive row prefix ``cs``: the position iota,
    light mask, total light weight ``csL`` (B, 1), light keys ``b`` and
    heavy keys ``A``."""
    B, Kp = s_sorted.shape
    nLc = nL.reshape(B, 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, Kp), 1)
    light = pos < nLc
    posf = pos.astype(jnp.float32)
    csL = jnp.sum(jnp.where(light, s_sorted, 0.0), axis=-1, keepdims=True)
    b = posf - (cs - s_sorted) + 1.0
    A = (cs - posf) + (nLc.astype(jnp.float32) - csL)
    return pos, light, csL, b, A


def _drained(pos, nL, rank):
    """Heavies: (j, i) = (heavy ordinal, lights drained when it empties)."""
    nLc = nL.reshape(-1, 1)
    j = pos - nLc
    return j, jnp.clip(rank - j, 0, nLc)


def _light_prefix(cs, nL, rank):
    """``PL(i) = cs[i-1]`` at every heavy position's drained-light count
    ``i`` — the one per-row gather of the assembly, done XLA-side
    (``take_along_axis``) for the kernel and the twin alike."""
    pos = jax.lax.broadcasted_iota(jnp.int32, cs.shape, 1)
    _j, i = _drained(pos, nL, rank)
    PLi = jnp.take_along_axis(cs, jnp.maximum(i - 1, 0), axis=-1)
    return jnp.where(i > 0, PLi, 0.0)


def _assemble(s_sorted, nL, rank, cs, PLi):
    """Closed-form table assembly from the partitioned order, its row
    prefix ``cs``, the merged sweep rank and the gathered light prefixes
    ``PLi``.  Returns ``(prob, apos)`` in sorted position space (``apos``
    = alias *position*; the caller maps positions back to original
    category ids and clamps pad overflow)."""
    B, Kp = s_sorted.shape
    pos, light, csL, b, A = _sweep_vals(s_sorted, nL, cs)
    nLc = nL.reshape(B, 1)
    # lights: the serving heavy is the first with A > b — rank arithmetic
    q = jnp.minimum(nLc + (rank - pos), Kp - 1)
    # heavies: lights drained when heavy j empties, then conservation
    j, i = _drained(pos, nL, rank)
    r = PLi + (cs - csL) - (i + j).astype(jnp.float32)
    prob = jnp.where(
        light, jnp.minimum(s_sorted, 1.0), jnp.clip(r, 0.0, 1.0)
    )
    apos = jnp.where(light, q, jnp.minimum(pos + 1, Kp - 1))
    return prob, apos


# ---------------------------------------------------------------------------
# The tiled Pallas assembly kernel
# ---------------------------------------------------------------------------


def _assemble_kernel(
    s_ref, nl_ref, rank_ref, cs_ref, pli_ref, prob_ref, apos_ref
):
    prob, apos = _assemble(
        s_ref[...].astype(jnp.float32), nl_ref[...], rank_ref[...],
        cs_ref[...], pli_ref[...],
    )
    prob_ref[...] = prob
    apos_ref[...] = apos


def alias_assemble_pallas(
    s_sorted: jnp.ndarray,
    nL: jnp.ndarray,
    rank: jnp.ndarray,
    cs: jnp.ndarray,
    PLi: jnp.ndarray,
    tb: int = 8,
    interpret: bool | None = None,
):
    """Tiled table assembly: (Bp, Kp) partitioned scaled weights (Kp a
    pow2), per-row light counts, merged ranks, row prefixes and gathered
    light prefixes -> (prob, apos), both (Bp, Kp).  ONE ``pallas_call``, grid
    ``(Bp//tb,)``."""
    interpret = runtime.resolve_interpret(interpret)
    Bp, Kp = s_sorted.shape
    tile = pl.BlockSpec((tb, Kp), lambda i: (i, 0))
    prob, apos = pl.pallas_call(
        _assemble_kernel,
        grid=(Bp // tb,),
        in_specs=[tile, pl.BlockSpec((tb, 1), lambda i: (i, 0)), tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Kp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, Kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(
        s_sorted.astype(jnp.float32),
        nL.astype(jnp.int32)[:, None],
        rank.astype(jnp.int32),
        cs.astype(jnp.float32),
        PLi.astype(jnp.float32),
    )
    return prob, apos
