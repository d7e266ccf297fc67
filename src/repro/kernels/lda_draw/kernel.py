"""Fused LDA z-draw kernels — the paper's inner loop without materialized weights.

The paper's Algorithm 8 *fuses* the theta-phi product with the butterfly
table construction so the (B, K) relative-probability table never round-trips
through main memory.  These kernels are the TPU-native statement of that
fusion (DESIGN.md §4):

  * the data-dependent fetches of ``theta[doc[s], :]`` and ``phi[w[s], :]``
    — the memory-coalescing problem the paper's warp-transposed loads
    solve — become **scalar-prefetch-driven BlockSpec index_maps**: the
    doc id selects the theta row group and the word id the phi row group
    (the aligned 8 rows holding the row: a TPU block's sublane dimension
    is a multiple of 8), the Pallas pipeline DMAs them into VMEM
    (double-buffered), and the kernel picks the row.  Theta is never
    ``jnp.repeat``-ed to one row per word position;
  * theta row x phi row -> weights, per-W-block sums, block selection and
    the in-block dyadic walk all happen in registers/VMEM;
  * HBM traffic per sample: one (8, Kp) group of theta and one of phi,
    nothing written but the index.

Tiled grid (DESIGN.md §3): ``grid = (B//tb, tb)``.  The inner dimension
streams one (theta row, phi row) product per sample into a (tb, Kp) VMEM
tile; the last inner step runs the whole fused draw — block sums,
in-kernel block selection, vectorized (tb, W) dyadic walk — for the tile
at once.  Kp (K padded to a multiple of W) must fit VMEM alongside the
tile — true by construction for LDA (K <= ~1k topics).

Three entry points:
  * ``lda_fused_draw_pallas``   — factored one-``pallas_call`` draw
    (theta (C, K), phi (V, K), per-sample doc/word ids, uniforms)
  * ``lda_blocksums_pallas``    — factored pass A: running per-W-block
    sums of the theta-phi products, (B, K//W), never forming (B, K)
    (the ``lda_kernel`` Categorical variant's table build)
  * ``lda_walk_pallas``         — factored pass B: re-reads only the
    aligned window around the selected W-block of each sample's theta/phi
    rows (table-in draw)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import runtime
from repro.kernels.butterfly_sample import kernel as _bk
from repro.kernels.butterfly_sample.kernel import (
    _block_sums,
    _draw_tile,
    _pick_row,
    _row_cumsum,
    _set_row,
    _walk_block,
    _window,
)


def _product_row(theta_ref, phi_ref, doc, word):
    """theta[doc] * phi[word] as a (1, L) row from their (8, L) row groups
    (the paper's line 16, fp32 accumulation)."""
    th = _pick_row(theta_ref[...].astype(jnp.float32), doc % 8)
    ph = _pick_row(phi_ref[...].astype(jnp.float32), word % 8)
    return th * ph


def _row_groups(L: int, tb: int, first, second):
    """(8, L) row-group BlockSpecs of theta and phi, selected by the
    scalar-prefetched doc / word id of the sample at grid step (i, r)."""
    return [
        pl.BlockSpec(
            (8, L), lambda i, r, *refs: (refs[first][i * tb + r] // 8, 0)
        ),
        pl.BlockSpec(
            (8, L), lambda i, r, *refs: (refs[second][i * tb + r] // 8, 0)
        ),
    ]


# ---------------------------------------------------------------------------
# Fused factored draw: ONE pallas_call over (B//tb, tb)
# ---------------------------------------------------------------------------


def _fused_factored_kernel(
    docs_ref, words_ref, theta_ref, phi_ref, u_ref, out_ref, w_acc, *, W: int, TB: int
):
    i, r = pl.program_id(0), pl.program_id(1)
    s = i * TB + r
    # one row of the (TB, Kp) product tile per inner grid step
    _set_row(w_acc, r, _product_row(theta_ref, phi_ref, docs_ref[s], words_ref[s]))

    @pl.when(r == TB - 1)
    def _draw():
        out_ref[...] = _draw_tile(w_acc[...], u_ref[...].astype(jnp.float32), W)


def lda_fused_draw_pallas(
    theta: jnp.ndarray,     # (C, Kp) document-topic weights
    phi: jnp.ndarray,       # (V, Kp) word-topic weights
    doc_ids: jnp.ndarray,   # (Bt,) int32 theta row per sample
    words: jnp.ndarray,     # (Bt,) int32 phi row per sample
    u: jnp.ndarray,         # (Bt,) uniforms
    W: int,
    tb: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-kernel fused draw; Bt % tb == 0, Kp % W == 0 (pad first)."""
    interpret = runtime.resolve_interpret(interpret)
    Bt = u.shape[0]
    Kp = theta.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bt // tb, tb),
        in_specs=_row_groups(Kp, tb, 0, 1) + [
            pl.BlockSpec((tb, 1), lambda i, r, *_: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, r, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tb, Kp), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_fused_factored_kernel, W=W, TB=tb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bt, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        doc_ids.astype(jnp.int32), words.astype(jnp.int32),
        theta, phi, u.astype(jnp.float32)[:, None],
    )
    return out[:, 0]


# ---------------------------------------------------------------------------
# Factored pass A: running block sums straight from the factors
# ---------------------------------------------------------------------------


def _factored_blocksum_kernel(
    docs_ref, words_ref, theta_ref, phi_ref, out_ref, w_acc, *, W: int, TB: int
):
    i, r = pl.program_id(0), pl.program_id(1)
    s = i * TB + r
    _set_row(w_acc, r, _product_row(theta_ref, phi_ref, docs_ref[s], words_ref[s]))

    @pl.when(r == TB - 1)
    def _sums():
        out_ref[...] = _row_cumsum(_block_sums(w_acc[...], W))


def lda_blocksums_pallas(
    theta: jnp.ndarray,
    phi: jnp.ndarray,
    doc_ids: jnp.ndarray,
    words: jnp.ndarray,
    W: int,
    tb: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Factored pass A: (Bt, Kp//W) *running* block sums of theta*phi —
    the (C*N, K) weight tensor never exists."""
    interpret = runtime.resolve_interpret(interpret)
    Bt = doc_ids.shape[0]
    Kp = theta.shape[1]
    nb = Kp // W
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bt // tb, tb),
        in_specs=_row_groups(Kp, tb, 0, 1),
        out_specs=pl.BlockSpec((tb, nb), lambda i, r, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tb, Kp), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_factored_blocksum_kernel, W=W, TB=tb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bt, nb), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(doc_ids.astype(jnp.int32), words.astype(jnp.int32), theta, phi)


# ---------------------------------------------------------------------------
# Factored pass B: walk only the selected W-block of each sample's rows
# ---------------------------------------------------------------------------


def _factored_walk_kernel(
    rows_ref, docs_ref, words_ref, jb_ref,
    theta_ref, phi_ref, run_ref, u_ref, out_ref, blk_acc, run_acc,
    *, W: int, TB: int,
):
    i, r = pl.program_id(0), pl.program_id(1)
    s = i * TB + r
    _set_row(blk_acc, r, _product_row(theta_ref, phi_ref, docs_ref[s], words_ref[s]))
    _set_row(run_acc, r, _pick_row(run_ref[...].astype(jnp.float32), rows_ref[s] % 8))

    @pl.when(r == TB - 1)
    def _walk():
        running = run_acc[...]
        nb = running.shape[1]
        stop = running[:, nb - 1:nb] * u_ref[...].astype(jnp.float32)
        out_ref[...] = _walk_block(running, stop, blk_acc[...], W)


def lda_walk_pallas(
    theta: jnp.ndarray,
    phi: jnp.ndarray,
    running: jnp.ndarray,   # (B, nb) running block sums (factored pass A)
    u: jnp.ndarray,         # (Bt,) uniforms
    rows: jnp.ndarray,      # (Bt,) sample index per draw (multi-draw tiles it)
    doc_ids: jnp.ndarray,   # (Bt,) theta row per draw (already rows-gathered)
    words: jnp.ndarray,     # (Bt,) phi row per draw (already rows-gathered)
    jb: jnp.ndarray,        # (Bt,) selected block per draw (DMA address only)
    W: int,
    tb: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Factored table-in draw: per sample, one aligned window of the
    theta and phi rows around the selected W-block (+ the running row)."""
    interpret = runtime.resolve_interpret(interpret)
    Bt = u.shape[0]
    Kp = theta.shape[1]
    nb = running.shape[1]
    win = _window(W, Kp)

    def at(i, r, rows_ref, docs_ref, words_ref, jb_ref, which):
        s = i * tb + r
        col = jb_ref[s] * W // win
        return {
            "theta": (docs_ref[s] // 8, col),
            "phi": (words_ref[s] // 8, col),
            "run": (rows_ref[s] // 8, 0),
            "tile": (i, 0),
        }[which]

    def spec(shape, which):
        return pl.BlockSpec(shape, functools.partial(at, which=which))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(Bt // tb, tb),
        in_specs=[
            spec((8, win), "theta"),
            spec((8, win), "phi"),
            spec((8, nb), "run"),
            spec((tb, 1), "tile"),
        ],
        out_specs=spec((tb, 1), "tile"),
        scratch_shapes=[
            pltpu.VMEM((tb, win), jnp.float32),
            pltpu.VMEM((tb, nb), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_factored_walk_kernel, W=W, TB=tb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bt, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        rows.astype(jnp.int32), doc_ids.astype(jnp.int32),
        words.astype(jnp.int32), jb.astype(jnp.int32),
        theta, phi, running, u.astype(jnp.float32)[:, None],
    )
    return out[:, 0]


# ---------------------------------------------------------------------------
# Jitted entry points (padding + legacy per-sample-theta signature)
# ---------------------------------------------------------------------------


def _pad_k(x, W: int):
    padK = (-x.shape[1]) % W
    return jnp.pad(x, ((0, 0), (0, padK))) if padK else x


# Samples per kernel launch.  The per-sample doc/word ids are scalar-
# prefetched into SMEM (1 MiB on v5e), so a longer sample list — a whole
# shard of a sharded sweep — runs as a ``lax.map`` over chunks this long.
_SMEM_SAMPLES = 32768


def _lda_draw_impl(theta, phi, doc_ids, words, u, W: int, tb: int, interpret):
    K = theta.shape[1]
    B = u.shape[0]
    if B > _SMEM_SAMPLES:
        n = -(-B // _SMEM_SAMPLES)
        pad = n * _SMEM_SAMPLES - B
        chunks = [
            jnp.pad(x, (0, pad)).reshape(n, _SMEM_SAMPLES)
            for x in (doc_ids, words, u)
        ]
        idx = jax.lax.map(
            lambda c: _lda_draw_impl(theta, phi, *c, W, tb, interpret), chunks
        )
        return idx.reshape(-1)[:B]
    thetap = _pad_k(theta, W)
    phip = _pad_k(phi, W)
    Kp = thetap.shape[1]
    tb = _bk._fused_tb(tb, Kp)
    padB = (-B) % tb
    if padB:
        doc_ids = jnp.pad(doc_ids, (0, padB))
        words = jnp.pad(words, (0, padB))
        u = jnp.pad(u.astype(jnp.float32), (0, padB), constant_values=0.5)
    if not _bk._fused_fits(tb, Kp, W):
        # the (tb, Kp) product tile would blow VMEM: take the factored
        # two-pass route (pass A streams factor rows, pass B touches one
        # window of each) — formula-identical to the fused kernel
        running = lda_blocksums_pallas(
            thetap, phip, doc_ids, words, W=W, tb=tb, interpret=interpret
        )
        jb = _bk._block_search(running, u)
        rows = jnp.arange(u.shape[0], dtype=jnp.int32)
        idx = lda_walk_pallas(
            thetap, phip, running, u, rows, doc_ids, words, jb,
            W=W, tb=tb, interpret=interpret,
        )
    else:
        idx = lda_fused_draw_pallas(
            thetap, phip, doc_ids, words, u, W=W, tb=tb, interpret=interpret
        )
    return jnp.minimum(idx[:B], K - 1)


@functools.partial(jax.jit, static_argnames=("W", "tb", "interpret"))
def lda_draw_docs_pallas(
    theta: jnp.ndarray,     # (C, K) per-document topic weights
    phi: jnp.ndarray,       # (V, K) word-topic weights
    doc_ids: jnp.ndarray,   # (B,) int32 document id per word position
    words: jnp.ndarray,     # (B,) int32 word ids
    u: jnp.ndarray,         # (B,) uniforms
    W: int = 32,
    tb: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Factored fused draw: theta rows selected by ``doc_ids`` through the
    BlockSpec index_map — no ``jnp.repeat`` row expansion anywhere."""
    return _lda_draw_impl(theta, phi, doc_ids, words, u, W, tb, interpret)


@functools.partial(jax.jit, static_argnames=("W", "tb", "interpret"))
def lda_draw_pallas(
    theta: jnp.ndarray,   # (B, K) per-sample topic weights
    phi: jnp.ndarray,     # (V, K) word-topic weights
    words: jnp.ndarray,   # (B,) int32 word ids
    u: jnp.ndarray,       # (B,) uniforms
    W: int = 32,
    tb: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Legacy signature: one theta row per sample (doc_ids = arange)."""
    B = theta.shape[0]
    doc_ids = jnp.arange(B, dtype=jnp.int32)
    return _lda_draw_impl(theta, phi, doc_ids, words, u, W, tb, interpret)
