"""Attention variants: GQA (qk-norm / softcap / sliding window), MLA
(compressed-latent, with the absorbed decode path), and cross-attention.

Masking is position-based so the same math serves train (full causal),
prefill (causal, cache write) and decode (one query against a long cache,
including sequence-sharded caches at 500k where GSPMD turns the masked
reduction into a flash-decoding-style partial-softmax combine — see
EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import apply_rope, head_rmsnorm, head_rmsnorm_spec
from repro.models.params import ParamSpec

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------


def attention_mask(
    q_pos: jnp.ndarray,  # (Sq,)
    k_pos: jnp.ndarray,  # (Sk,)
    causal: bool = True,
    window=0,            # python int or traced int32 scalar (0 = full)
    k_valid: Optional[jnp.ndarray] = None,  # (Sk,) bool
) -> jnp.ndarray:
    """(Sq, Sk) boolean mask: True = attend.  ``window`` may be traced (it
    is per-layer scan data), so the windowing is a where, not a branch."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    window = jnp.asarray(window, jnp.int32)
    win_m = k_pos[None, :] > q_pos[:, None] - window
    m &= jnp.where(window > 0, win_m, True)
    if k_valid is not None:
        m &= k_valid[None, :]
    return m


CHUNKED_THRESHOLD = 4096  # q lengths above this use the chunked path
Q_CHUNK = 256


def _repeat_kv(k, H):
    """(B,S,KV,hd) -> (B,S,H,hd): every query head gets its own copy of
    its group's K/V head.  Only ``_sdpa_chunked`` (prompts above
    ``CHUNKED_THRESHOLD``) repeats; ``_sdpa`` reads the K/V heads as
    stored."""
    KV = k.shape[2]
    if KV == H:
        return k
    return jnp.repeat(k, H // KV, axis=2)


def _group_scores(q, k, softcap: float = 0.0):
    """q (B,Sq,H,hd) against k (B,Sk,KV,hd) -> fp32 scores (B,KV,G,Sq,Sk).

    Grouped-query contraction: q splits into (KV, G = H // KV) and each
    group's G heads contract against their K/V head as stored, so a
    decode step reads the cache once instead of copying it G times.
    Scaled, and soft-capped when ``softcap`` > 0.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Sq, KV, H // KV, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    scores = jnp.einsum("bqkge,bske->bkgqs", qg, k).astype(jnp.float32) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    return scores


def _group_context(probs, v):
    """probs (B,KV,G,Sq,Sk) against v (B,Sk,KV,hv) -> (B,Sq,KV,G,hv),
    in v's dtype."""
    return jnp.einsum("bkgqs,bskv->bqkgv", probs.astype(v.dtype), v)


def _sdpa(q, k, v, mask, softcap: float = 0.0):
    """q (B,Sq,H,hd)  k (B,Sk,KV,hd)  v (B,Sk,KV,hv) -> (B,Sq,H,hv), with
    a (Sq, Sk) mask, or (B, Sq, Sk) for one per row; fp32 scores and
    softmax (``_group_scores``)."""
    B, Sq, H, _ = q.shape
    mask = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    scores = jnp.where(mask, _group_scores(q, k, softcap), NEG_INF)
    out = _group_context(jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(B, Sq, H, v.shape[-1])


# cache leaves with a sequence axis, (B, S, ...) a layer and (L, B, S, ...)
# stacked; every other cache leaf (SSM conv/state) is per-row state
SEQ_LEAVES = frozenset({"k", "v", "c_kv", "k_pe"})


def is_seq_leaf(path) -> bool:
    """Whether a cache tree path (``tree_map_with_path``) ends in one of
    ``SEQ_LEAVES``."""
    return any(getattr(k, "key", None) in SEQ_LEAVES for k in path)


def rows_in_place() -> bool:
    """How a decode step writes its new K/V (or latent) rows, chosen when
    the step is traced.  Without an activation mesh (every single-chip
    engine) the layers only return their (B, 1, ...) rows, and
    ``write_rows`` puts them into the stacked cache after the layer scan,
    in place where the cache is donated.  Under a mesh the cache may be
    sharded by sequence, where a write at a traced position would
    all-gather it, so each layer rewrites its cache with an elementwise
    one-hot ``where`` (``_cache_update``) that stays in its shards."""
    from repro.dist import sharding as shd

    return shd._ACT_CTX.get("mesh") is None


def write_rows(stack, rows, pos):
    """Write one decode step's rows (L, B, 1, ...) into a stacked cache
    (L, B, S, ...) at each row's position (``pos`` scalar or (B,), each
    below S): B row writes, in place where the stack is donated."""
    B = stack.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    rows = rows.astype(stack.dtype)
    zero = jnp.int32(0)
    for b in range(B):
        start = (zero, jnp.int32(b), pos[b]) + (zero,) * (stack.ndim - 3)
        stack = jax.lax.dynamic_update_slice(stack, rows[:, b:b + 1], start)
    return stack


def _cache_update(cache_arr, new, pos):
    """A layer's cache (B, S, ...) with one decode step's rows ``new``
    (B, 1, ...) written at ``pos`` (scalar or (B,)), for caches under an
    activation mesh (``rows_in_place``): a one-hot masked update, so a
    sequence-sharded cache never leaves its shards (a full read and write
    of the local shard instead of an all-gather per layer)."""
    from repro.dist import sharding as shd

    B, S = cache_arr.shape[:2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    oh = jnp.arange(S)[None, :] == pos[:, None]               # (B, S)
    oh = oh.reshape(oh.shape + (1,) * (cache_arr.ndim - 2))
    upd = jnp.where(oh, new.astype(cache_arr.dtype), cache_arr)
    axes = ("batch", "act_kv") + (None,) * (cache_arr.ndim - 2)
    return shd.constrain_activation(upd, axes)


def _decode_mask(S: int, pos, window=0):
    """(B, S) mask of the stored positions a decode query at ``pos``
    ((B,) or scalar, broadcast to B rows) attends besides its own token:
    those before it, and within ``window`` of it when > 0 (may be
    traced)."""
    k_pos = jnp.arange(S)[None, :]
    qp = jnp.asarray(pos, jnp.int32).reshape(-1, 1)
    win = jnp.asarray(window, jnp.int32)
    return (k_pos < qp) & jnp.where(win > 0, k_pos > qp - win, True)


def _softmax_with_new(scores, new_score):
    """One float32 softmax over the stored positions' scores (..., S),
    masked already, and the new token's own (...): (probs over the stored
    positions, the new token's prob)."""
    probs = jax.nn.softmax(jnp.concatenate([scores, new_score[..., None]], -1), -1)
    return probs[..., :-1], probs[..., -1]


def _sdpa_decode(q, k_new, v_new, k, v, mask, softcap: float = 0.0):
    """One decode query a row against the stored cache and its own token.

    q (B,1,H,hd); k_new (B,1,KV,hd), v_new (B,1,KV,hv) the token's own;
    k (B,S,KV,hd), v (B,S,KV,hv) the stored cache, read as it is, of
    which ``mask`` (B, S) selects the positions before the query.  The
    same work as ``_sdpa`` over a cache holding the new row at the
    query's position: grouped-query contraction, fp32 scores, softcap and
    one softmax over every attended position.  The score matrix's key
    axis is pinned to the cache's sequence sharding under a mesh
    (flash-decoding layout), so GSPMD reduces with small psums instead of
    all-gathering the cache.
    """
    from repro.dist.sharding import constrain_activation

    B, _, H, _ = q.shape
    scores = constrain_activation(_group_scores(q, k, softcap),
                                  ("batch", None, None, None, "act_kv"))
    own = _group_scores(q, k_new, softcap)[..., 0]
    scores = jnp.where(mask[:, None, None, None], scores, NEG_INF)
    probs, p_own = _softmax_with_new(scores, own)
    f32 = jnp.float32
    out = (_group_context(probs, v).astype(f32)
           + jnp.einsum("bkgq,bqkv->bqkgv", p_own, v_new.astype(f32)))
    return out.astype(v.dtype).reshape(B, 1, H, v.shape[-1])


def _sdpa_chunked(
    q, k, v, q_pos, k_pos, *, causal, window, k_valid=None, softcap=0.0,
    q_chunk: int = Q_CHUNK,
):
    """Flash-style q-chunked attention: scans over query chunks so the
    (Sq, Sk) score matrix never materializes — the reason 32k prefill fits
    even for archs whose head counts don't divide the model axis (hymba's
    25, minicpm3's 40).  Softmax per chunk is exact (full K per chunk)."""
    B, Sq, H, hd = q.shape
    k, v = _repeat_kv(k, H), _repeat_kv(v, H)
    pad = (-Sq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad))
    nc = q.shape[1] // q_chunk
    qc = q.reshape(B, nc, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(nc, q_chunk)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def chunk_attn(_, inp):
        qi, pi = inp
        scores = jnp.einsum("bqhe,bshe->bhqs", qi, k).astype(jnp.float32) * scale
        if softcap > 0:
            scores = jnp.tanh(scores / softcap) * softcap
        m = attention_mask(pi, k_pos, causal=causal, window=window, k_valid=k_valid)
        scores = jnp.where(m[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return None, jnp.einsum("bhqs,bshv->bqhv", probs, v)

    _, out = jax.lax.scan(chunk_attn, None, (qc, pc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nc * q_chunk, H, -1)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    spec = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head")),
        "wo": ParamSpec((h, hd, d), ("heads", "head", "embed")),
    }
    if cfg.qk_norm:
        spec["q_norm"] = head_rmsnorm_spec(hd)
        spec["k_norm"] = head_rmsnorm_spec(hd)
    return spec


def gqa_project_qkv(params, x, positions, cfg: ModelConfig):
    """x (B,S,D) -> q (B,S,H,hd), k,v (B,S,KV,hd), with RoPE + qk-norm."""
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    if cfg.qk_norm:
        q = head_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attend(
    params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    causal: bool = True,
    window: int = 0,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    cache_pos: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Self-attention over a full block (train/prefill) or one decode step.

    Decode mode: ``cache`` holds (k, v) of length S_max; ``cache_pos`` is the
    query's position, scalar or (B,) per row; ``positions`` is (B?, 1) the
    same for RoPE.  The step returns the token's own (k, v) rows, which the
    caller writes after the layer scan, or under a mesh the rewritten
    cache (``rows_in_place``).
    """
    B, S, _ = x.shape
    q, k, v = gqa_project_qkv(params, x, positions, cfg)
    if cache is None:
        if S > CHUNKED_THRESHOLD:
            out = _sdpa_chunked(
                q, k, v, positions, positions, causal=causal, window=window,
                softcap=cfg.attn_softcap,
            )
        else:
            mask = attention_mask(positions, positions, causal=causal, window=window)
            out = _sdpa(q, k, v, mask, cfg.attn_softcap)
        new_cache = None
        kv_for_prefill = (k, v)
    else:
        mask = _decode_mask(cache["k"].shape[1], cache_pos, window)
        out = _sdpa_decode(q, k, v, cache["k"], cache["v"], mask, cfg.attn_softcap)
        if rows_in_place():
            new_cache = {"k": k, "v": v}
        else:
            new_cache = {"k": _cache_update(cache["k"], k, cache_pos),
                         "v": _cache_update(cache["v"], v, cache_pos)}
        kv_for_prefill = None
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return y, (new_cache if cache is not None else kv_for_prefill)


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": ParamSpec((batch, max_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head"), init="zeros"),
        "v": ParamSpec((batch, max_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head"), init="zeros"),
    }


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------


def cross_attention_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    return {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head")),
        "wo": ParamSpec((h, hd, d), ("heads", "head", "embed")),
    }


def cross_attend(params, x, memory_kv, cfg: ModelConfig, memory_valid=None):
    """x (B,Sq,D) attends to precomputed memory (k, v) (B,Sk,KV,hd)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k, v = memory_kv
    Sk = k.shape[1]
    if S > CHUNKED_THRESHOLD:
        out = _sdpa_chunked(
            q, k, v, jnp.arange(S), jnp.arange(Sk), causal=False, window=0,
            k_valid=memory_valid, softcap=cfg.attn_softcap,
        )
    else:
        mask = jnp.ones((S, Sk), bool)
        if memory_valid is not None:
            mask = mask & memory_valid[None, :]
        out = _sdpa(q, k, v, mask, cfg.attn_softcap)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"])


def cross_memory(params, memory, cfg: ModelConfig):
    """Precompute cross-attention (k, v) from encoder output (B,Sk,D)."""
    k = jnp.einsum("bsd,dnh->bsnh", memory, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", memory, params["wv"])
    return k, v


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------


def mla_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim
    if m.q_lora_rank is None:
        q = {"wq": ParamSpec((d, h, qk + m.qk_rope_head_dim), ("embed", "heads", "head"))}
    else:
        q = {
            "wdq": ParamSpec((d, m.q_lora_rank), ("embed", "q_lora")),
            "q_norm": {"scale": ParamSpec((m.q_lora_rank,), ("q_lora",), init="ones")},
            "wuq": ParamSpec(
                (m.q_lora_rank, h, qk + m.qk_rope_head_dim), ("q_lora", "heads", "head")
            ),
        }
    return {
        **q,
        "wdkv": ParamSpec(
            (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora")
        ),
        "kv_norm": {"scale": ParamSpec((m.kv_lora_rank,), ("kv_lora",), init="ones")},
        "wuk": ParamSpec((m.kv_lora_rank, h, qk), ("kv_lora", "heads", "head")),
        "wuv": ParamSpec((m.kv_lora_rank, h, m.v_head_dim), ("kv_lora", "heads", "head")),
        "wo": ParamSpec((h, m.v_head_dim, d), ("heads", "head", "embed")),
    }


# the latent (and query-latent) RMSNorms: DeepSeek-V2/V3 build them with
# their norm's default eps, not the config's rms_norm_eps
MLA_NORM_EPS = 1e-6


def _mla_latents(params, x, positions, cfg: ModelConfig):
    """x -> (c_kv (B,S,r), k_pe (B,S,rope)) with norm + RoPE applied."""
    m: MLAConfig = cfg.mla
    dkv = jnp.einsum("bsd,dr->bsr", x, params["wdkv"])
    c_kv, k_pe = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank :]
    c_kv = _vec_rmsnorm(params["kv_norm"], c_kv, MLA_NORM_EPS)
    return c_kv, _mla_rope(k_pe, positions, cfg)


def _mla_rope(x, positions, cfg: ModelConfig):
    """RoPE on the rope part of q or k.  With ``rope_interleave`` the dims
    are first de-interleaved (pair 2i, 2i+1 becomes i, i + rope/2), as the
    published DeepSeek-V3 code does before its half-split rotation."""
    if cfg.mla.rope_interleave:
        r = x.shape[-1]
        x = x.reshape(x.shape[:-1] + (r // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    return apply_rope(x, positions, cfg.rope_theta)


def _vec_rmsnorm(p, x, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def _mla_queries(params, x, positions, cfg: ModelConfig):
    m: MLAConfig = cfg.mla
    if m.q_lora_rank is None:
        q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    else:
        cq = _vec_rmsnorm(params["q_norm"], jnp.einsum("bsd,dr->bsr", x, params["wdq"]), MLA_NORM_EPS)
        q = jnp.einsum("bsr,rnh->bsnh", cq, params["wuq"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_pe = _mla_rope(q[..., m.qk_nope_head_dim :], positions, cfg)
    return q_nope, q_pe


def mla_attend_full(params, x, positions, cfg: ModelConfig):
    """Prefill/train: expand latents to per-head k/v (the 'naive' mode)."""
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    c_kv, k_pe = _mla_latents(params, x, positions, cfg)
    q_nope, q_pe = _mla_queries(params, x, positions, cfg)
    k_nope = jnp.einsum("bsr,rnh->bsnh", c_kv, params["wuk"])
    v = jnp.einsum("bsr,rnh->bsnh", c_kv, params["wuv"])
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], k_nope.shape[:3] + (m.qk_rope_head_dim,))],
        -1,
    )
    if S > CHUNKED_THRESHOLD:
        out = _sdpa_chunked(
            q, k, v, positions, positions, causal=True, window=0,
            softcap=cfg.attn_softcap,
        )
    else:
        mask = attention_mask(positions, positions, causal=True)
        out = _sdpa(q, k, v, mask, cfg.attn_softcap)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return y, {"c_kv": c_kv, "k_pe": k_pe}


def mla_attend_decode(params, x, cache, cache_pos, cfg: ModelConfig):
    """Absorbed decode: score directly against the latent cache.

    q_c = q_nope @ W_uk  per head; scores = q_c . c_kv + q_pe . k_pe;
    ctx = probs . c_kv; y = (ctx @ W_uv) @ wo — the per-token cost is
    O(H*(nope*r + r)) and the cache is (r + rope) per position instead of
    2*H*hd: the reason minicpm3 fits 32k cheaply.  Returns the token's own
    latent rows, or under a mesh the rewritten cache, as ``gqa_attend``.
    """
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape  # S == 1
    cache_pos = jnp.asarray(cache_pos)
    positions = (
        cache_pos[:, None] if cache_pos.ndim == 1
        else jnp.full((S,), 0, jnp.int32) + cache_pos
    )
    c_new, kpe_new = _mla_latents(params, x, positions, cfg)
    c_kv, k_pe = cache["c_kv"], cache["k_pe"]
    q_nope, q_pe = _mla_queries(params, x, positions, cfg)
    q_c = jnp.einsum("bsnh,rnh->bsnr", q_nope, params["wuk"])
    scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_head_dim + m.qk_rope_head_dim))
    f32 = jnp.float32
    # the stored positions before each row's query, then its own latents
    scores = (
        jnp.einsum("bsnr,btr->bnst", q_c, c_kv, preferred_element_type=f32)
        + jnp.einsum("bsnh,bth->bnst", q_pe, k_pe, preferred_element_type=f32)
    ) * scale
    own = (
        jnp.einsum("bsnr,bsr->bns", q_c.astype(f32), c_new.astype(f32))
        + jnp.einsum("bsnh,bsh->bns", q_pe.astype(f32), kpe_new.astype(f32))
    ) * scale
    mask = _decode_mask(c_kv.shape[1], cache_pos)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs, p_own = _softmax_with_new(scores, own)
    ctx = (
        jnp.einsum("bnst,btr->bsnr", probs.astype(c_kv.dtype), c_kv).astype(f32)
        + jnp.einsum("bns,bsr->bsnr", p_own, c_new.astype(f32))
    ).astype(c_kv.dtype)
    out = jnp.einsum("bsnr,rnh->bsnh", ctx, params["wuv"])
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    if rows_in_place():
        return y, {"c_kv": c_new, "k_pe": kpe_new}
    return y, {"c_kv": _cache_update(c_kv, c_new, cache_pos),
               "k_pe": _cache_update(k_pe, kpe_new, cache_pos)}


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    m: MLAConfig = cfg.mla
    return {
        "c_kv": ParamSpec((batch, max_len, m.kv_lora_rank), ("batch", "kv_seq", None), init="zeros"),
        "k_pe": ParamSpec((batch, max_len, m.qk_rope_head_dim), ("batch", "kv_seq", None), init="zeros"),
    }
