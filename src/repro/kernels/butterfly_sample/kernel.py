"""Fused two-pass categorical sampling kernel (TPU adaptation of the paper).

The paper's end-to-end win is *never materializing the full (B, K) prefix
table*: the butterfly table is "just adequate" to reconstruct the partial
sums a binary search touches.  On TPU the analogous HBM-traffic statement
is (DESIGN.md §2):

  pass A  (``_blocksum_kernel``)  streams (tb, tk) weight tiles through
          VMEM and emits only the per-W-block sums — HBM: read B*K,
          write B*K/W.
  pass B  (``_walk_kernel``)      re-reads *only the window holding the
          selected W-block* per sample (scalar-prefetch drives the
          BlockSpec index_map — the Pallas analogue of the data-dependent
          fetch the GPU warp does), builds the dyadic segment table
          (the TPU-adapted butterfly; Fenwick layout) and walks it
          add-only, log2(W) steps.

Total HBM traffic ~ B*K*(1 + 1/W) + one (8, 128) window per sample versus
>= 3*B*K for the classic prefix-table route (write prefix, re-read during
search with scattered gathers).

Tiled-grid layout (DESIGN.md §3).  Both draw-side kernels run a *tiled*
grid rather than one grid step per sample:

  * ``_fused_draw_kernel`` is the one-``pallas_call`` end-to-end draw:
    grid ``(B//tb,)``, each step loads a (tb, Kp) weight tile, reduces it
    to block sums, selects each row's W-block and walks the dyadic table
    — block selection is folded into the kernel, and the whole (tb, W)
    tile walks its log2(W) levels in lock-step on the VPU.
  * ``_walk_kernel`` is the table-in pass B for prebuilt ``(wp, running)``
    state: grid ``(B//tb, tb)``; the inner grid dimension streams one
    scalar-prefetch-selected window per sample into a (tb, window) VMEM
    accumulator, and the last inner step runs the vectorized selection +
    walk for the whole tile.  Only the block *address* ``jb`` is computed
    outside (the DMA engine needs it before the kernel body runs);
    stop/lo and the selection arithmetic are recomputed in-kernel from
    the fetched running-sum rows.

TPU tiling.  A block's last two dimensions must be multiples of (8, 128)
or span the array, so per-sample fetches move the aligned (8, window)
group that holds the sample's row and pick the row in-kernel
(``_pick_row``).  Mosaic lowers neither ``cumsum`` nor a lane-splitting
reshape, so block sums, running sums, block extraction and the Fenwick
build are products with small 0/1 matrices on the MXU at full f32
precision (``_dot``); every per-row dynamic index is a one-hot masked
reduction over a ``broadcasted_iota``.  The same kernel bodies run
compiled on TPU and under interpret mode elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import rng as _rng
from repro.kernels import runtime


# ---------------------------------------------------------------------------
# Shared tile math: vectorized (TB, W) selection + dyadic walk
# ---------------------------------------------------------------------------


def _iota(shape, dim: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot(a, b) -> jnp.ndarray:
    """f32 product at full precision: with a 0/1 right operand every
    output is an exact-product f32 sum (the MXU's default single bf16
    pass would round the weights)."""
    return jnp.dot(
        a, b, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _block_sums(w: jnp.ndarray, W: int) -> jnp.ndarray:
    """(TB, L) -> (TB, L // W) per-W-block sums (one indicator product)."""
    L = w.shape[1]
    shape = (L, L // W)
    ind = (_iota(shape, 0) // W == _iota(shape, 1)).astype(jnp.float32)
    return _dot(w, ind)


def _row_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sums along the lanes of a (TB, n) tile: products
    with an upper-triangular 0/1 matrix, 128 lanes at a time with a
    carried total once n is a multiple of 128."""
    TB, n = x.shape
    c = 128 if n % 128 == 0 else n
    tri = (_iota((c, c), 0) <= _iota((c, c), 1)).astype(jnp.float32)
    if c == n:
        return _dot(x, tri)
    outs, carry = [], jnp.zeros((TB, 1), jnp.float32)
    for k in range(n // c):
        pre = _dot(x[:, k * c:(k + 1) * c], tri) + carry
        outs.append(pre)
        carry = pre[:, c - 1:c]
    return jnp.concatenate(outs, axis=1)


def _pick_row(group: jnp.ndarray, r) -> jnp.ndarray:
    """Row ``r`` of an (8, L) row group as a (1, L) tile."""
    sub = _iota((group.shape[0], 1), 0) == r
    return jnp.sum(jnp.where(sub, group, 0.0), axis=0, keepdims=True)


def _set_row(acc_ref, r, row) -> None:
    """Write a (1, L) row into row ``r`` of a (TB, L) VMEM accumulator."""
    sub = _iota((acc_ref.shape[0], 1), 0) == r
    acc_ref[...] = jnp.where(sub, row, acc_ref[...])


def _extract_block(w: jnp.ndarray, jb: jnp.ndarray, W: int) -> jnp.ndarray:
    """(TB, L) tile, (TB, 1) block ids -> (TB, W): each row's W-block."""
    L = w.shape[1]
    wm = jnp.where(_iota(w.shape, 1) // W == jb, w, 0.0)
    pick = (_iota((L, W), 0) % W == _iota((L, W), 1)).astype(jnp.float32)
    return _dot(wm, pick)


def _fenwick_tile(t: jnp.ndarray, W: int) -> jnp.ndarray:
    """Fenwick layout of every row of a (TB, W) tile: position d with
    ntz(d+1)=l holds S[d-2^l+1..d] — one product with the 0/1 matrix
    whose column d covers that range."""
    i, d = _iota((W, W), 0), _iota((W, W), 1)
    low = (d + 1) & -(d + 1)
    fen = ((i <= d) & (i > d - low)).astype(jnp.float32)
    return _dot(t, fen)


def _descent_tile(t, stop, lo, W: int):
    """Vectorized add-only descent (Alg. 10, TPU-adapted): every row of the
    (TB, W) Fenwick tile walks its log2(W) levels in lock-step; the
    per-row dynamic read is a one-hot masked lane reduction.  ``stop``
    and ``lo`` are (TB, 1); returns (TB, 1) in-block offsets."""
    lane = _iota(t.shape, 1)
    acc = lo
    R = jnp.zeros(stop.shape, jnp.int32)
    for b in range(int(np.log2(W)) - 1, -1, -1):
        bit = 1 << b
        y = jnp.sum(
            jnp.where(lane == R + (bit - 1), t, 0.0), axis=1, keepdims=True
        )
        mid = acc + y
        go_high = stop >= mid
        acc = jnp.where(go_high, mid, acc)
        R = jnp.where(go_high, R + bit, R)
    return R


def _select_tile(running, stop):
    """In-kernel block-level search (the paper's Alg. 9): smallest block c
    with stop < running[c], plus the exclusive prefix ``lo`` below it.
    ``running``: (TB, nb) running block sums; ``stop``: (TB, 1)."""
    nb = running.shape[1]
    jb = jnp.clip(
        jnp.sum((running <= stop).astype(jnp.int32), axis=1, keepdims=True),
        0, nb - 1,
    )
    lo = jnp.sum(
        jnp.where(_iota(running.shape, 1) == jb - 1, running, 0.0),
        axis=1, keepdims=True,
    )
    return jb, lo


def _walk_block(running, stop, blk, W: int):
    """Selection + walk for a tile whose rows hold the window around their
    selected block: ``blk`` is (TB, window) with the block at lanes
    ``(jb * W) % window``.  Returns (TB, 1) indices into [0, Kp)."""
    jb, lo = _select_tile(running, stop)
    sel = _extract_block(blk, (jb * W) % blk.shape[1] // W, W)
    R = _descent_tile(_fenwick_tile(sel, W), stop, lo, W)
    return jb * W + R


def _draw_tile(w, u, W: int):
    """The complete fused draw for one (TB, Kp) tile already in VMEM:
    block sums -> running sums -> block selection -> Fenwick build ->
    add-only descent.  ``u`` is (TB, 1); returns (TB, 1) int32 indices
    into [0, Kp)."""
    running = _row_cumsum(_block_sums(w, W))                     # (TB, nb)
    nb = running.shape[1]
    return _walk_block(running, running[:, nb - 1:nb] * u, w, W)


def _window(W: int, Kp: int) -> int:
    """Lane width of the aligned window pass B fetches around a W-block:
    128 lanes (or W when wider) when rows are 128-aligned, else the row."""
    if Kp % 128:
        return Kp
    return max(W, 128)


# ---------------------------------------------------------------------------
# Pass A: per-W-block sums (tiled over both axes)
# ---------------------------------------------------------------------------


def _place(bs: jnp.ndarray, off, n: int) -> jnp.ndarray:
    """(TB, m) -> (TB, n) with ``bs`` at lanes [off, off + m), zeros
    elsewhere (one 0/1 product: no dynamic lane store needed)."""
    m = bs.shape[1]
    put = (_iota((m, n), 0) + off == _iota((m, n), 1)).astype(jnp.float32)
    return _dot(bs, put)


def _blocksum_kernel(w_ref, out_ref, *, W: int):
    # the (tb, nb) output block stays resident across the K axis; each
    # (tb, tk) tile adds its block sums at its own lane offset
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tk = w_ref.shape[1]
    bs = _block_sums(w_ref[...].astype(jnp.float32), W)
    out_ref[...] += _place(bs, j * (tk // W), out_ref.shape[1])


def blocksums_pallas(
    weights: jnp.ndarray, W: int, tb: int, tk: int, interpret: bool | None = None
) -> jnp.ndarray:
    """(B, K) -> (B, K//W) per-block sums; B % tb == 0, K % tk == 0, tk % W == 0."""
    interpret = runtime.resolve_interpret(interpret)
    B, K = weights.shape
    nb = K // W
    return pl.pallas_call(
        functools.partial(_blocksum_kernel, W=W),
        grid=(B // tb, K // tk),
        in_specs=[pl.BlockSpec((tb, tk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tb, nb), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nb), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(weights)


# ---------------------------------------------------------------------------
# Fused end-to-end draw: ONE pallas_call, grid (B//tb,)
# ---------------------------------------------------------------------------

# VMEM budget for the fused draw's (tb, Kp) weight tile (fp32 bytes).
# Beyond it the row tile shrinks, and past tb=8 the draw falls back to the
# two-pass route, whose pass A streams (tb, tk) tiles and whose pass B
# touches one aligned window per sample — safe at any K (vocab-scale
# included).
_FUSED_TILE_BYTES = 4 << 20
# ... and for the 0/1 matrices the fused tile math builds in VMEM
# (block indicator (Kp, Kp/W) and block extractor (Kp, W)).  The v5e
# compiler accepts the fused kernels at every (W, Kp) on this budget's
# edge (tests/test_tpu_compile.py), and it keeps every K <= 4096 fused.
_FUSED_MATRIX_BYTES = 16 << 20


def _fused_tb(tb: int, Kp: int) -> int:
    tb = runtime.row_tile(tb)
    while tb > 8 and tb * Kp * 4 > _FUSED_TILE_BYTES:
        tb //= 2
    return tb


def _fused_fits(tb: int, Kp: int, W: int) -> bool:
    """Whether the one-kernel route fits VMEM for a (tb, Kp) tile."""
    return (
        tb * Kp * 4 <= _FUSED_TILE_BYTES
        and Kp * (Kp // W + W) * 4 <= _FUSED_MATRIX_BYTES
    )


def _fused_draw_kernel(w_ref, u_ref, out_ref, *, W: int):
    w = w_ref[...].astype(jnp.float32)                 # (TB, Kp)
    out_ref[...] = _draw_tile(w, u_ref[...].astype(jnp.float32), W)


def fused_draw_pallas(
    wp: jnp.ndarray, u: jnp.ndarray, W: int, tb: int, interpret: bool | None = None
) -> jnp.ndarray:
    """One-kernel fused draw over padded (Bp, Kp) weights; ``u`` (Bp,).
    Bp % tb == 0, Kp % W == 0.  Block selection happens in-kernel — no
    XLA round-trip between the block-sum and walk phases."""
    interpret = runtime.resolve_interpret(interpret)
    Bp, Kp = wp.shape
    out = pl.pallas_call(
        functools.partial(_fused_draw_kernel, W=W),
        grid=(Bp // tb,),
        in_specs=[
            pl.BlockSpec((tb, Kp), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(wp, u[:, None])
    return out[:, 0]


# ---------------------------------------------------------------------------
# Fused draw with IN-KERNEL counter RNG: the (B,) uniform operand is gone
# ---------------------------------------------------------------------------


def _tile_uniforms(meta_ref, tb: int) -> jnp.ndarray:
    """(tb, 1) Threefry uniforms for this grid step's global rows; the
    (1, 3) SMEM ``meta`` block is [s0, s1, row_offset]."""
    i = pl.program_id(0)
    s0, s1, off = meta_ref[0, 0], meta_ref[0, 1], meta_ref[0, 2]
    rows = off + (i * tb + _iota((tb, 1), 0)).astype(jnp.uint32)
    b0, _ = _rng.threefry2x32(s0, s1, rows, jnp.zeros_like(rows))
    return _rng.bits_to_uniform(b0)


def _fused_draw_rng_kernel(meta_ref, w_ref, out_ref, *, W: int, tb: int, hw: bool):
    """Fused draw whose uniforms are generated inside the kernel from a
    (seed, global-row) counter — no u operand, no key-split chain.

    ``meta_ref`` is a (1, 3) uint32 block: [s0, s1, row_offset].  The
    offset is the shard's first global row, so a row-sharded launch draws
    the same bits any other shard layout would (DESIGN.md §5).  ``hw``
    selects the TPU hardware PRNG (per-tile-seeded, TPU-native only);
    the default is the portable Threefry twin — ~40 vector uint32 ops,
    bit-identical to the XLA-side generator.
    """
    if hw:
        i = pl.program_id(0)
        s0, s1, off = meta_ref[0, 0], meta_ref[0, 1], meta_ref[0, 2]
        pltpu.prng_seed(s0, s1, off + jnp.uint32(i * tb))
        bits = pltpu.prng_random_bits((tb, 1))
        u = _rng.bits_to_uniform(pltpu.bitcast(bits, jnp.uint32))
    else:
        u = _tile_uniforms(meta_ref, tb)
    w = w_ref[...].astype(jnp.float32)
    out_ref[...] = _draw_tile(w, u, W)


def _meta(seed, row_offset) -> jnp.ndarray:
    return jnp.concatenate(
        [
            jnp.asarray(seed, jnp.uint32).reshape(2),
            jnp.asarray(row_offset).astype(jnp.uint32).reshape(1),
        ]
    ).reshape(1, 3)


def fused_draw_rng_pallas(
    wp: jnp.ndarray,
    seed: jnp.ndarray,
    row_offset,
    W: int,
    tb: int,
    hw: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-kernel fused draw over padded (Bp, Kp) weights with in-kernel
    RNG.  ``seed`` is a (2,) uint32 pair (already domain-tagged);
    ``row_offset`` the first row's global id (traced scalar is fine)."""
    interpret = runtime.resolve_interpret(interpret)
    Bp, Kp = wp.shape
    out = pl.pallas_call(
        functools.partial(_fused_draw_rng_kernel, W=W, tb=tb, hw=hw),
        grid=(Bp // tb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tb, Kp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(_meta(seed, row_offset), wp)
    return out[:, 0]


@functools.partial(
    jax.jit, static_argnames=("W", "tb", "tk", "hw", "interpret")
)
def butterfly_sample_rng_pallas(
    weights: jnp.ndarray,
    seed: jnp.ndarray,
    row_offset=0,
    W: int = 32,
    tb: int = 8,
    tk: int = 512,
    hw: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Seed-driven fused draw: (B, K) weights + (2,) uint32 seed -> (B,).

    The uniform for row r is ``uniform(tag(seed), row_offset + r)`` —
    generated *inside* the fused kernel (the (B,) operand and its HBM
    read are deleted); the VMEM-overflow fallback takes the two-pass
    route with the same counters derived XLA-side (pass B's block search
    needs u before the DMA addresses exist), so both routes draw
    bit-identical indices.
    """
    B, K = weights.shape
    seed2 = _rng.fold(jnp.asarray(seed, jnp.uint32), _rng.TAG_U, 0)
    padK = (-K) % W
    Kp = K + padK
    tb = _fused_tb(tb, Kp)
    if not _fused_fits(tb, Kp, W):
        if hw:
            # the two-pass route derives u XLA-side (the block search needs
            # it before the DMA addresses exist) — hardware bits can't be
            # reproduced there, so silently switching streams would break
            # the fixed-seed reproducibility this function promises
            raise ValueError(
                f"hw_rng needs the fused (tb={tb}, Kp={Kp}) weight tile to "
                "fit the VMEM budget; this shape falls back to the two-pass "
                "route — use the default Threefry RNG (hw=False)"
            )
        wp, running = _build_sums_impl(weights, W, tb, tk, interpret)
        u = _rng.row_uniforms(seed2, row_offset, B)
        return _draw_from_sums_impl(wp, running, u, B, K, W, tb, interpret)
    padB = (-B) % tb
    wp = jnp.pad(weights, ((0, padB), (0, padK)))
    idx = fused_draw_rng_pallas(
        wp, seed2, row_offset, W, tb, hw=hw, interpret=interpret
    )
    return jnp.minimum(idx[:B], K - 1)


@functools.partial(
    jax.jit, static_argnames=("S", "B", "K", "W", "tb", "interpret")
)
def sample_from_block_sums_rng_pallas(
    wp: jnp.ndarray,
    running: jnp.ndarray,
    seed: jnp.ndarray,
    row_offset=0,
    S: int = 1,
    B: int = 0,
    K: int = 0,
    W: int = 32,
    tb: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Seed-driven table-in pass B: S draws per row from prebuilt
    (wp, running) state, uniforms derived from (global row, draw index)
    counters — one launch for all S*B walks, launch count independent of
    S, no key-split chain.  Returns (B,) when S == 1, else (S, B)."""
    seed2 = _rng.fold(jnp.asarray(seed, jnp.uint32), _rng.TAG_U, 0)
    if S == 1:
        u = _rng.row_uniforms(seed2, row_offset, B)
    else:
        u = _rng.multi_row_uniforms(seed2, row_offset, B, S)
    return _draw_from_sums_impl(wp, running, u, B, K, W, tb, interpret)


# ---------------------------------------------------------------------------
# Fused truncated decode: top-k/top-p/min-p folded into the draw (no sort)
# ---------------------------------------------------------------------------
#
# Truncation is a per-row value threshold (repro.sampling.transforms), and
# a threshold is found by bisection on the value axis — so the fused draw
# gains one extra in-VMEM phase instead of a (B, K) sort: the weight tile
# is already resident for pass A, each bisection step is one masked
# reduction over it, and the masked tile feeds the same block-sum/select/
# walk pipeline.  No sorted copy, no extra HBM sweep (DESIGN.md §7).


def _trunc_tile(w, params, iters: int) -> jnp.ndarray:
    """Truncate a (TB, Kp) weight tile in VMEM by its rows' canonical
    ``[k, p, min_p]`` parameter triple (sequential semantics: top-p sees
    only the top-k survivors).  Disabled stages (k <= 0, p >= 1,
    min_p <= 0) pass through; returns the masked tile.

    The threshold math is :func:`repro.sampling.transforms
    .threshold_column` itself — pure jnp reductions plus a
    ``fori_loop`` bisection over uint32 float bit patterns, which traces
    inside the Pallas kernel body exactly as it does in XLA.  One
    implementation means the fused mask can never drift from the twin
    (or the sorted oracle) by a boundary/tie semantic fixed in only one
    place."""
    from repro.sampling import transforms as _tr

    return jnp.where(w >= _tr.threshold_column(w, params, iters), w, 0.0)


def _fused_trunc_draw_kernel(w_ref, u_ref, prm_ref, out_ref, *, W: int, iters: int):
    w = w_ref[...].astype(jnp.float32)
    wm = _trunc_tile(w, prm_ref[...].astype(jnp.float32), iters)
    out_ref[...] = _draw_tile(wm, u_ref[...].astype(jnp.float32), W)


def fused_trunc_draw_pallas(
    wp: jnp.ndarray,
    u: jnp.ndarray,
    params: jnp.ndarray,
    W: int,
    tb: int,
    iters: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-kernel truncated draw over padded (Bp, Kp) weights: threshold
    search + masking + block sums + selection + walk, all on the one
    VMEM-resident tile.  ``params`` is (Bp, 3) float32 ``[k, p, min_p]``
    rows (traced — per-row heterogeneous truncation in one executable)."""
    interpret = runtime.resolve_interpret(interpret)
    Bp, Kp = wp.shape
    out = pl.pallas_call(
        functools.partial(_fused_trunc_draw_kernel, W=W, iters=iters),
        grid=(Bp // tb,),
        in_specs=[
            pl.BlockSpec((tb, Kp), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, 3), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(wp, u[:, None], params)
    return out[:, 0]


def _fused_trunc_draw_rng_kernel(
    meta_ref, prm_ref, w_ref, out_ref, *, W: int, tb: int, iters: int
):
    """Truncated fused draw with in-kernel counter RNG (the sharded/serve
    fast path): uniforms from (seed, global row) Threefry counters, then
    the same in-VMEM threshold + draw pipeline."""
    u = _tile_uniforms(meta_ref, tb)
    w = w_ref[...].astype(jnp.float32)
    wm = _trunc_tile(w, prm_ref[...].astype(jnp.float32), iters)
    out_ref[...] = _draw_tile(wm, u, W)


def fused_trunc_draw_rng_pallas(
    wp: jnp.ndarray,
    seed: jnp.ndarray,
    row_offset,
    params: jnp.ndarray,
    W: int,
    tb: int,
    iters: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = runtime.resolve_interpret(interpret)
    Bp, Kp = wp.shape
    out = pl.pallas_call(
        functools.partial(_fused_trunc_draw_rng_kernel, W=W, tb=tb, iters=iters),
        grid=(Bp // tb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tb, 3), lambda i: (i, 0)),
            pl.BlockSpec((tb, Kp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(_meta(seed, row_offset), params, wp)
    return out[:, 0]


# -- two-pass truncated route (vocab-scale tiles): masked pass A + walk ----


def _masked_blocksum_kernel(w_ref, tau_ref, out_ref, *, W: int):
    """Pass A over *masked* weights: the truncation mask is applied to the
    streamed (tb, tk) tile in VMEM — the masked (B, K) matrix never hits
    HBM."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)
    wm = jnp.where(w >= tau_ref[...].astype(jnp.float32), w, 0.0)
    tk = w.shape[1]
    out_ref[...] += _place(_block_sums(wm, W), j * (tk // W), out_ref.shape[1])


def masked_blocksums_pallas(
    weights: jnp.ndarray,
    tau: jnp.ndarray,
    W: int,
    tb: int,
    tk: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = runtime.resolve_interpret(interpret)
    B, K = weights.shape
    nb = K // W
    return pl.pallas_call(
        functools.partial(_masked_blocksum_kernel, W=W),
        grid=(B // tb, K // tk),
        in_specs=[
            pl.BlockSpec((tb, tk), lambda i, j: (i, j)),
            pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, nb), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nb), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(weights, tau[:, None])


# ---------------------------------------------------------------------------
# Pass B (table-in): tiled walk over prebuilt (wp, running) state
# ---------------------------------------------------------------------------


def _walk_kernel(
    rows_ref, jb_ref, wblk_ref, run_ref, u_ref, *rest, W: int, TB: int,
    masked: bool,
):
    """Stream each sample's window (the aligned (8, window) row group
    around its scalar-prefetch-selected W-block) and its running-sum row
    group into the tile accumulators; the last inner step selects and
    walks the whole tile.  ``masked``: re-mask the streamed raw weights
    by their row's threshold before the Fenwick build (the running sums
    arrive masked from masked pass A, so stop/lo/jb are consistent)."""
    if masked:
        tau_ref, out_ref, blk_acc, run_acc = rest
    else:
        out_ref, blk_acc, run_acc = rest
    i, r = pl.program_id(0), pl.program_id(1)
    sub = rows_ref[i * TB + r] % 8
    _set_row(blk_acc, r, _pick_row(wblk_ref[...].astype(jnp.float32), sub))
    _set_row(run_acc, r, _pick_row(run_ref[...].astype(jnp.float32), sub))

    @pl.when(r == TB - 1)
    def _walk():
        running = run_acc[...]
        nb = running.shape[1]
        stop = running[:, nb - 1:nb] * u_ref[...].astype(jnp.float32)
        blk = blk_acc[...]
        if masked:
            blk = jnp.where(blk >= tau_ref[...].astype(jnp.float32), blk, 0.0)
        # recompute the block selection in-kernel (bit-identical to the
        # jb operand that addressed the DMA) so lo/stop never round-trip
        out_ref[...] = _walk_block(running, stop, blk, W)


def _walk_call(wp, running, u, rows, jb, tau, W: int, tb: int, interpret):
    interpret = runtime.resolve_interpret(interpret)
    Bt = u.shape[0]
    Kp = wp.shape[1]
    nb = running.shape[1]
    win = _window(W, Kp)

    def at(spec_fn):
        return lambda i, r, rows_ref, jb_ref: spec_fn(i, rows_ref[i * tb + r],
                                                      jb_ref[i * tb + r])

    in_specs = [
        pl.BlockSpec((8, win), at(lambda i, row, b: (row // 8, b * W // win))),
        pl.BlockSpec((8, nb), at(lambda i, row, b: (row // 8, 0))),
        pl.BlockSpec((tb, 1), at(lambda i, row, b: (i, 0))),
    ]
    operands = [wp, running, u.astype(jnp.float32)[:, None]]
    if tau is not None:
        in_specs.append(pl.BlockSpec((tb, 1), at(lambda i, row, b: (i, 0))))
        operands.append(tau.astype(jnp.float32)[:, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bt // tb, tb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, 1), at(lambda i, row, b: (i, 0))),
        scratch_shapes=[
            pltpu.VMEM((tb, win), jnp.float32),
            pltpu.VMEM((tb, nb), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _walk_kernel, W=W, TB=tb, masked=tau is not None
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bt, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rows.astype(jnp.int32), jb.astype(jnp.int32), *operands)
    return out[:, 0]


def walk_pallas(
    wp: jnp.ndarray,
    running: jnp.ndarray,
    u: jnp.ndarray,
    rows: jnp.ndarray,
    jb: jnp.ndarray,
    W: int,
    tb: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Tiled pass B: draw sample i from row ``rows[i]`` of the prebuilt
    ``(wp, running)`` pair, re-reading only the window around W-block
    ``jb[i]``.

    ``rows``/``jb``/``u`` all have length Bt (a multiple of ``tb``); the
    ``rows`` indirection lets S draws per distribution share one kernel
    launch (multi-draw tiles ``arange(B)`` S times).  ``jb`` must be the
    block-level search result for (rows, u) — it is consumed ONLY by the
    BlockSpec index_map (the DMA address); the selection arithmetic is
    recomputed in-kernel from the fetched running rows.  ``wp`` and
    ``running`` have a multiple of 8 rows.
    """
    return _walk_call(wp, running, u, rows, jb, None, W, tb, interpret)


def walk_trunc_pallas(
    wp: jnp.ndarray,
    running: jnp.ndarray,
    u: jnp.ndarray,
    tau: jnp.ndarray,
    rows: jnp.ndarray,
    jb: jnp.ndarray,
    W: int,
    tb: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Tiled masked pass B; ``tau`` has length Bt like ``u``/``rows``
    (already gathered per sample for multi-draw)."""
    return _walk_call(wp, running, u, rows, jb, tau, W, tb, interpret)


# ---------------------------------------------------------------------------
# Table-in/table-out halves + fused end-to-end draw (jitted entry points)
# ---------------------------------------------------------------------------


def _pass_a_tk(K: int, W: int, tk: int) -> int:
    """Pass-A category tile: a multiple of W, clamped to the W-padded row
    for small K; a lane-aligned (multiple of 128) tile otherwise."""
    tk = max(W, min(tk, int(np.ceil(K / W)) * W))
    if tk % W:
        raise ValueError(f"tk={tk} must be a multiple of W={W}")
    if tk < K and tk % 128:
        tk = -(-tk // max(W, 128)) * max(W, 128)
    return tk


def _build_sums_impl(weights, W: int, tb: int, tk: int, interpret):
    """Pass A as a table-out step: pad, blocksum, running-sum.

    Returns ``(wp, running)`` — the padded weights (pass B re-reads the
    selected W-block from them) and the (Bp, Kp//W) running block sums.
    This pair IS the kernel strategy's reusable precomputed state (the
    analogue of the fenwick/butterfly tables for the other variants).
    """
    B, K = weights.shape
    tb = runtime.row_tile(tb)
    tk = _pass_a_tk(K, W, tk)
    padB = (-B) % tb
    padK = (-K) % tk
    wp = jnp.pad(weights, ((0, padB), (0, padK)))
    bs = blocksums_pallas(wp, W, tb, tk, interpret=interpret)   # (Bp, Kp//W)
    running = jnp.cumsum(bs, axis=1)
    return wp, running


def _block_search(running_rows, u):
    """XLA-side block-level search producing the pass-B DMA addresses:
    the smallest block whose running sum exceeds stop = total * u."""
    nb = running_rows.shape[1]
    stop = running_rows[:, -1] * u.astype(jnp.float32)
    return jnp.clip(
        jnp.sum(running_rows <= stop[:, None], axis=1).astype(jnp.int32),
        0, nb - 1,
    )


def _sample_rows(u, B: int, tb: int):
    """Flatten (B,) or (S, B) uniforms into one padded sample list:
    returns (uf, rows, Bt) with ``rows`` the source row of each sample."""
    S = u.shape[0] if u.ndim == 2 else 1
    uf = u.reshape(-1).astype(jnp.float32)                       # (S*B,)
    rows = jnp.tile(jnp.arange(B, dtype=jnp.int32), S)
    Bt = S * B
    padT = (-Bt) % tb
    if padT:
        uf = jnp.pad(uf, (0, padT))
        rows = jnp.pad(rows, (0, padT))
    return uf, rows, Bt


def _draw_from_sums_impl(wp, running, u, B: int, K: int, W: int, tb: int, interpret):
    """Pass B as a table-in step.  ``u`` is (B,) for one draw per row or
    (S, B) for S draws per row (the multi-draw decode path); ``B``/``K``
    are the unpadded shape."""
    tb = runtime.row_tile(tb)
    uf, rows, Bt = _sample_rows(u, B, tb)
    jb = _block_search(running[rows], uf)
    idx = walk_pallas(wp, running, uf, rows, jb, W, tb, interpret=interpret)
    idx = jnp.minimum(idx[:Bt], K - 1)
    return idx.reshape(u.shape) if u.ndim == 2 else idx


def _build_masked_sums_impl(weights, tau, W: int, tb: int, tk: int, interpret):
    """Masked pass A: pad, masked blocksums, running sums.  Padded rows
    carry tau = 0, so their all-zero weights stay all-zero sums."""
    B, K = weights.shape
    tb = runtime.row_tile(tb)
    tk = _pass_a_tk(K, W, tk)
    padB = (-B) % tb
    padK = (-K) % tk
    wp = jnp.pad(weights, ((0, padB), (0, padK)))
    taup = jnp.pad(tau.astype(jnp.float32), (0, padB))
    bs = masked_blocksums_pallas(wp, taup, W, tb, tk, interpret=interpret)
    running = jnp.cumsum(bs, axis=1)
    return wp, taup, running


def _trunc_draw_from_sums_impl(
    wp, taup, running, u, B: int, K: int, W: int, tb: int, interpret
):
    """Masked pass B with the multi-draw ``rows`` indirection; mirrors
    ``_draw_from_sums_impl`` plus the per-sample threshold gather."""
    tb = runtime.row_tile(tb)
    uf, rows, Bt = _sample_rows(u, B, tb)
    jb = _block_search(running[rows], uf)
    idx = walk_trunc_pallas(
        wp, running, uf, taup[rows], rows, jb, W, tb, interpret=interpret
    )
    idx = jnp.minimum(idx[:Bt], K - 1)
    return idx.reshape(u.shape) if u.ndim == 2 else idx


def _pad_params(params, padB: int) -> jnp.ndarray:
    """Grow a (B, 3) param block by neutral [k=0, p=1, m=0] rows."""
    params = jnp.asarray(params, jnp.float32)
    if not padB:
        return params
    neutral = jnp.broadcast_to(
        jnp.asarray([0.0, 1.0, 0.0], jnp.float32), (padB, 3)
    )
    return jnp.concatenate([params, neutral], axis=0)


@functools.partial(
    jax.jit, static_argnames=("W", "tb", "tk", "iters", "interpret")
)
def butterfly_sample_truncated_pallas(
    weights: jnp.ndarray,
    u: jnp.ndarray,
    params: jnp.ndarray,
    W: int = 32,
    tb: int = 8,
    tk: int = 512,
    iters: int = 32,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Truncated draw: (B, K) weights, (B,) uniforms, (B, 3) canonical
    ``[k, p, min_p]`` params -> (B,) indices from the renormalized
    truncated distribution.

    Small tiles run the ONE-kernel fused route (threshold search in
    VMEM); vocab-scale tiles compute per-row thresholds XLA-side
    (``repro.sampling.transforms``), then run masked pass A + masked
    pass B — the masked (B, K) matrix never materializes in HBM and no
    route ever sorts."""
    B, K = weights.shape
    params = jnp.asarray(params, jnp.float32)
    padK = (-K) % W
    Kp = K + padK
    tb = _fused_tb(tb, Kp)
    if not _fused_fits(tb, Kp, W):
        from repro.sampling import transforms as _tr

        tau = _tr.thresholds_from_params(weights, params, iters=iters)
        wp, taup, running = _build_masked_sums_impl(
            weights, tau, W, tb, tk, interpret
        )
        return _trunc_draw_from_sums_impl(
            wp, taup, running, u, B, K, W, tb, interpret
        )
    padB = (-B) % tb
    wp = jnp.pad(weights, ((0, padB), (0, padK)))
    up = jnp.pad(u.astype(jnp.float32), (0, padB), constant_values=0.5)
    idx = fused_trunc_draw_pallas(
        wp, up, _pad_params(params, padB), W, tb, iters, interpret=interpret
    )
    return jnp.minimum(idx[:B], K - 1)


@functools.partial(
    jax.jit, static_argnames=("W", "tb", "tk", "iters", "interpret")
)
def butterfly_sample_truncated_rng_pallas(
    weights: jnp.ndarray,
    seed: jnp.ndarray,
    params: jnp.ndarray,
    row_offset=0,
    W: int = 32,
    tb: int = 8,
    tk: int = 512,
    iters: int = 32,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Seed-driven truncated fused draw (the sharded serving fast path):
    uniforms from (seed, global row) counters — in-kernel on the fused
    route, XLA-side on the two-pass fallback, bit-identical either way."""
    B, K = weights.shape
    params = jnp.asarray(params, jnp.float32)
    seed2 = _rng.fold(jnp.asarray(seed, jnp.uint32), _rng.TAG_U, 0)
    padK = (-K) % W
    Kp = K + padK
    tb = _fused_tb(tb, Kp)
    if not _fused_fits(tb, Kp, W):
        from repro.sampling import transforms as _tr

        tau = _tr.thresholds_from_params(weights, params, iters=iters)
        wp, taup, running = _build_masked_sums_impl(
            weights, tau, W, tb, tk, interpret
        )
        u = _rng.row_uniforms(seed2, row_offset, B)
        return _trunc_draw_from_sums_impl(
            wp, taup, running, u, B, K, W, tb, interpret
        )
    padB = (-B) % tb
    wp = jnp.pad(weights, ((0, padB), (0, padK)))
    idx = fused_trunc_draw_rng_pallas(
        wp, seed2, row_offset, _pad_params(params, padB), W, tb, iters,
        interpret=interpret,
    )
    return jnp.minimum(idx[:B], K - 1)


@functools.partial(jax.jit, static_argnames=("W", "tb", "tk", "interpret"))
def build_block_sums_pallas(
    weights: jnp.ndarray,
    W: int = 32,
    tb: int = 8,
    tk: int = 512,
    interpret: bool | None = None,
):
    """Jitted table-out entry point: (B, K) weights -> (wp, running)."""
    return _build_sums_impl(weights, W, tb, tk, interpret)


@functools.partial(jax.jit, static_argnames=("B", "K", "W", "tb", "interpret"))
def sample_from_block_sums_pallas(
    wp: jnp.ndarray,
    running: jnp.ndarray,
    u: jnp.ndarray,
    B: int,
    K: int,
    W: int = 32,
    tb: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Jitted table-in entry point: draw from prebuilt (wp, running).
    ``u`` may be (B,) or (S, B) — the latter runs all S*B walks in one
    tiled kernel launch."""
    return _draw_from_sums_impl(wp, running, u, B, K, W, tb, interpret)


@functools.partial(jax.jit, static_argnames=("W", "tb", "tk", "interpret"))
def butterfly_sample_pallas(
    weights: jnp.ndarray,
    u: jnp.ndarray,
    W: int = 32,
    tb: int = 8,
    tk: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Draw one index per row of (B, K) weights; u (B,) uniforms in [0,1).

    ONE fused pallas_call: each (tb, Kp) weight tile is loaded once and
    the block-sum/select/walk pipeline runs entirely in VMEM.  Pads B to
    a multiple of ``tb`` and K to a multiple of ``W`` (zero weights are
    never selected).  When the tile would blow the VMEM budget
    (vocab-scale K), the draw transparently takes the two-pass route —
    pass A streamed in (tb, tk) tiles, tiled pass B — which is
    formula-identical (``test_table_in_matches_fused`` pins this).
    """
    B, K = weights.shape
    padK = (-K) % W
    Kp = K + padK
    tb = _fused_tb(tb, Kp)
    if not _fused_fits(tb, Kp, W):
        wp, running = _build_sums_impl(weights, W, tb, tk, interpret)
        return _draw_from_sums_impl(wp, running, u, B, K, W, tb, interpret)
    padB = (-B) % tb
    wp = jnp.pad(weights, ((0, padB), (0, padK)))
    up = jnp.pad(u.astype(jnp.float32), (0, padB), constant_values=0.5)
    idx = fused_draw_pallas(wp, up, W, tb, interpret=interpret)
    return jnp.minimum(idx[:B], K - 1)
