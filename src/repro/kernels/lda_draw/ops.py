"""Public wrappers for the fused LDA z-draw kernels.

Two implementations of the same factored draw live behind every entry
point here:

* ``impl="pallas"`` — the tiled Pallas kernels in :mod:`kernel` (compiled
  natively on TPU; interpret-mode emulation elsewhere), and
* ``impl="xla"``   — a pure-XLA twin that performs the identical
  block-sum / block-select / in-block walk *without ever forming the
  (B, K) weight tensor*: pass A scans W-wide column slices of the factors
  (every intermediate is (B, W) or (B, nb)), pass B gathers only each
  sample's selected W-block.  This is what non-TPU backends run — the
  zero-materialization property holds on every backend, not just where
  Pallas compiles.

``impl=None`` picks Pallas on TPU and the XLA twin elsewhere, mirroring
the ``interpret`` policy in :mod:`repro.kernels.runtime`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import rng as _rng
from repro.kernels import runtime
from repro.kernels.lda_draw.kernel import (
    _pad_k,
    lda_blocksums_pallas,
    lda_draw_docs_pallas,
    lda_draw_pallas,
    lda_fused_draw_pallas,
    lda_walk_pallas,
)


def _resolve_impl(impl: Optional[str]) -> str:
    if impl is None:
        return "xla" if runtime.default_interpret() else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be 'pallas' or 'xla', got {impl!r}")
    return impl


# ---------------------------------------------------------------------------
# Pure-XLA twin (zero-materialization by construction)
# ---------------------------------------------------------------------------


def _xla_tk(Kp: int, W: int) -> int:
    """Column-tile for the XLA twin's pass A: per-W-block slices at small
    K, ~128-lane tiles beyond (measured optimum on CPU; either beats the
    materializing path by 2x+ at K >= 1024)."""
    return W if Kp <= 512 else max(W, 128)


def _xla_running(thetap, phip, doc_ids, words, W: int):
    """(Bt, nb) running block sums of theta[doc]*phi[word], streamed in
    (Bt, TK) column tiles — the (Bt, K) product never materializes.

    The tile loop is unrolled (fully fused by XLA) up to 64 tiles and
    falls back to a ``lax.scan`` beyond — factored workloads are
    topic-scale (K <= ~1k), so the unrolled path is the norm."""
    Kp = thetap.shape[1]
    TK = _xla_tk(Kp, W)
    padK = (-Kp) % TK
    if padK:
        thetap = jnp.pad(thetap, ((0, 0), (0, padK)))
        phip = jnp.pad(phip, ((0, 0), (0, padK)))
    nt = (Kp + padK) // TK

    def tile(c):
        th = jax.lax.dynamic_slice_in_dim(thetap, c * TK, TK, axis=1)[doc_ids]
        ph = jax.lax.dynamic_slice_in_dim(phip, c * TK, TK, axis=1)[words]
        prod = th.astype(jnp.float32) * ph.astype(jnp.float32)   # (Bt, TK)
        return prod.reshape(prod.shape[0], TK // W, W).sum(-1)

    if nt <= 64:
        cols = [tile(c) for c in range(nt)]
        bs = cols[0] if nt == 1 else jnp.concatenate(cols, axis=-1)
    else:
        _, stacked = jax.lax.scan(
            lambda c, _: (c + 1, tile(c)), 0, None, length=nt
        )                                                        # (nt, Bt, nb_t)
        bs = jnp.moveaxis(stacked, 0, 1).reshape(stacked.shape[1], -1)
    # zero-padded tail blocks contribute nothing; keep exactly Kp//W blocks
    return jnp.cumsum(bs, axis=-1)[:, : Kp // W]


def _xla_walk(thetap, phip, running_rows, u, doc_ids, words, W: int):
    """In-block draw from factored state: gathers exactly one W-block of
    theta and phi per sample (the pass-B traffic statement, in XLA)."""
    nb = running_rows.shape[1]
    stop = running_rows[:, -1] * u.astype(jnp.float32)
    jb = jnp.clip(
        jnp.sum(running_rows <= stop[:, None], axis=1).astype(jnp.int32), 0, nb - 1
    )
    lo = jnp.where(
        jb > 0,
        jnp.take_along_axis(running_rows, jnp.maximum(jb - 1, 0)[:, None], axis=1)[
            :, 0
        ],
        jnp.zeros_like(stop),
    )
    cols = jb[:, None] * W + jnp.arange(W, dtype=jnp.int32)[None, :]   # (Bt, W)
    sel = thetap[doc_ids[:, None], cols].astype(jnp.float32) * phip[
        words[:, None], cols
    ].astype(jnp.float32)
    prefix = jnp.cumsum(sel, axis=-1) + lo[:, None]
    r = jnp.sum(prefix <= stop[:, None], axis=1).astype(jnp.int32)
    return jb * W + jnp.minimum(r, W - 1)


def _xla_fused_draw(thetap, phip, doc_ids, words, u, W: int):
    running = _xla_running(thetap, phip, doc_ids, words, W)
    return _xla_walk(thetap, phip, running, u, doc_ids, words, W)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def lda_draw(theta, phi, words, u, W: int = 32, tb: int = 8,
             interpret: bool | None = None):
    """Legacy fused draw: z[b] ~ Categorical(theta[b,:] * phi[words[b],:]),
    one theta row per sample.  Always the Pallas kernel (DESIGN.md §4)."""
    return lda_draw_pallas(theta, phi, words, u, W=W, tb=tb, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("W", "tb", "impl", "interpret"))
def lda_draw_factored(
    theta,            # (C, K) per-document topic weights
    phi,              # (V, K) word-topic weights
    doc_ids,          # (B,) int32 document id per word position
    words,            # (B,) int32 word id per word position
    u,                # (B,) uniforms
    W: int = 32,
    tb: int = 8,
    impl: Optional[str] = None,
    interpret: bool | None = None,
):
    """Fused factored draw — the (C*N, K) weight tensor never materializes.

    Theta rows are selected by ``doc_ids`` (no ``jnp.repeat`` expansion);
    on TPU this is ONE ``pallas_call``, elsewhere the XLA twin."""
    K = theta.shape[1]
    B = u.shape[0]
    if _resolve_impl(impl) == "pallas":
        return lda_draw_docs_pallas(
            theta, phi, doc_ids, words, u, W=W, tb=tb, interpret=interpret
        )
    idx = _xla_fused_draw(
        _pad_k(theta, W), _pad_k(phi, W),
        doc_ids.astype(jnp.int32), words.astype(jnp.int32), u, W,
    )
    return jnp.minimum(idx[:B], K - 1)


@functools.partial(jax.jit, static_argnames=("W", "tb", "impl", "interpret"))
def lda_draw_factored_rng(
    theta,
    phi,
    doc_ids,
    words,
    seed,
    row_offset=0,
    W: int = 32,
    tb: int = 8,
    impl: Optional[str] = None,
    interpret: bool | None = None,
):
    """Seed-driven fused factored draw: the (B,) uniform buffer is
    replaced by counter RNG — u[b] = uniform(tag(seed), row_offset + b) —
    so a mesh-sharded Gibbs sweep passes one replicated (2,) seed and its
    shard's global row offset instead of splitting keys per shard/draw.
    Weights still never materialize (same kernels as
    :func:`lda_draw_factored`)."""
    B = words.shape[0]
    seed2 = _rng.fold(jnp.asarray(seed, jnp.uint32), _rng.TAG_U, 0)
    u = _rng.row_uniforms(seed2, row_offset, B)
    return lda_draw_factored(
        theta, phi, doc_ids, words, u, W=W, tb=tb, impl=impl,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("K", "S", "W", "tb", "impl", "interpret")
)
def lda_draw_from_running_rng(
    thetap,
    phip,
    running,
    seed,
    doc_ids,
    words,
    K: int,
    S: int = 1,
    row_offset=0,
    W: int = 32,
    tb: int = 8,
    impl: Optional[str] = None,
    interpret: bool | None = None,
):
    """Seed-driven factored pass B: S draws per sample from prebuilt
    running block sums, all S*B walks in one launch, uniforms from
    (global row, draw index) counters."""
    B = words.shape[0]
    seed2 = _rng.fold(jnp.asarray(seed, jnp.uint32), _rng.TAG_U, 0)
    if S == 1:
        u = _rng.row_uniforms(seed2, row_offset, B)
    else:
        u = _rng.multi_row_uniforms(seed2, row_offset, B, S)
    return lda_draw_from_running(
        thetap, phip, running, u, doc_ids, words, K=K, W=W, tb=tb, impl=impl,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("W", "tb", "impl", "interpret"))
def lda_build_running(
    theta, phi, doc_ids, words, W: int = 32, tb: int = 8,
    impl: Optional[str] = None, interpret: bool | None = None,
):
    """Factored pass A: (padded theta, padded phi, (B, nb) running block
    sums) — the ``lda_kernel`` Categorical variant's table build."""
    thetap, phip = _pad_k(theta, W), _pad_k(phi, W)
    doc_ids = doc_ids.astype(jnp.int32)
    words = words.astype(jnp.int32)
    if _resolve_impl(impl) == "pallas":
        B = doc_ids.shape[0]
        tb = runtime.row_tile(tb)
        padB = (-B) % tb
        dp = jnp.pad(doc_ids, (0, padB)) if padB else doc_ids
        wp = jnp.pad(words, (0, padB)) if padB else words
        running = lda_blocksums_pallas(
            thetap, phip, dp, wp, W=W, tb=tb, interpret=interpret
        )[:B]
    else:
        running = _xla_running(thetap, phip, doc_ids, words, W)
    return thetap, phip, running


@functools.partial(jax.jit, static_argnames=("K", "W", "tb", "impl", "interpret"))
def lda_draw_from_running(
    thetap, phip, running, u, doc_ids, words, K: int,
    W: int = 32, tb: int = 8,
    impl: Optional[str] = None, interpret: bool | None = None,
):
    """Factored pass B (table-in): draw from prebuilt running block sums,
    touching only each sample's selected W-block of theta and phi.

    ``u`` is (B,) for one draw per sample or (S, B) for S draws — the
    multi-draw case runs all S*B walks in one tiled kernel launch."""
    multi = u.ndim == 2
    S = u.shape[0] if multi else 1
    B = u.shape[-1]
    uf = u.reshape(-1).astype(jnp.float32)
    rows = jnp.tile(jnp.arange(B, dtype=jnp.int32), S)
    docs_t = doc_ids.astype(jnp.int32)[rows]
    words_t = words.astype(jnp.int32)[rows]
    if _resolve_impl(impl) == "pallas":
        from repro.kernels.butterfly_sample.kernel import _block_search

        Bt = S * B
        tb = runtime.row_tile(tb)
        padT = (-Bt) % tb
        if padT:
            uf = jnp.pad(uf, (0, padT))
            rows = jnp.pad(rows, (0, padT))
            docs_t = jnp.pad(docs_t, (0, padT))
            words_t = jnp.pad(words_t, (0, padT))
        jb = _block_search(running[rows], uf)
        idx = lda_walk_pallas(
            thetap, phip, running, uf, rows, docs_t, words_t, jb,
            W=W, tb=tb, interpret=interpret,
        )[:Bt]
    else:
        idx = _xla_walk(thetap, phip, running[rows], uf, docs_t, words_t, W)
    idx = jnp.minimum(idx, K - 1)
    return idx.reshape(S, B) if multi else idx
