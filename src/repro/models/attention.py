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


def _sdpa(q, k, v, mask, softcap: float = 0.0, kv_sharded: bool = False):
    """q (B,Sq,H,hd)  k (B,Sk,KV,hd)  v (B,Sk,KV,hv) -> (B,Sq,H,hv).

    Grouped-query contraction: q splits into (KV, G = H // KV) and each
    group's G heads contract against their K/V head as stored, so a
    decode step reads the cache once instead of copying it G times.

    fp32 scores/softmax; bf16 inputs stay bf16 on the contraction output.
    ``kv_sharded``: pin the score matrix's key axis to the cache's seq
    sharding (flash-decoding layout) so GSPMD reduces with tiny psums
    instead of all-gathering the cache.
    """
    from repro.dist.sharding import constrain_activation

    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Sq, KV, H // KV, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    scores = jnp.einsum("bqkge,bske->bkgqs", qg, k).astype(jnp.float32) * scale
    if kv_sharded:
        scores = constrain_activation(scores, ("batch", None, None, None, "act_kv"))
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    # (Sq, Sk) masks broadcast over batch; (B, Sq, Sk) masks are per-row
    # (continuous batching: each slot attends its own prefix length)
    scores = jnp.where(
        mask[None, None, None] if mask.ndim == 2 else mask[:, None, None],
        scores, NEG_INF,
    )
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskv->bqkgv", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def _cache_update(cache_arr, new, pos):
    """Write one decode step into the cache.

    ``pos`` is the scalar write position shared by the batch, or a (B,)
    vector of per-row positions (continuous batching: each slot writes at
    its own sequence length).

    Baseline: dynamic_update_slice (fast slice write, but GSPMD must
    all-gather a seq-sharded cache to update at a traced position).  Under
    the activation-sharding lever — and always for per-row positions —
    a one-hot masked update: elementwise, so the cache never leaves its
    shards (full read+write instead of a slice write: ~67MB/layer locally
    vs multi-GB of all-gather per layer)."""
    from repro.dist import sharding as shd

    pos = jnp.asarray(pos)
    if pos.ndim == 1:
        S = cache_arr.shape[1]
        oh = jnp.arange(S)[None, :] == pos[:, None]           # (B, S)
        oh = oh.reshape(oh.shape + (1,) * (cache_arr.ndim - 2))
        upd = jnp.where(oh, new.astype(cache_arr.dtype), cache_arr)
        if shd._ACT_CTX.get("mesh") is not None:
            axes = ("batch", "act_kv") + (None,) * (cache_arr.ndim - 2)
            upd = shd.constrain_activation(upd, axes)
        return upd
    if shd._ACT_CTX.get("mesh") is None:
        return jax.lax.dynamic_update_slice_in_dim(cache_arr, new, pos, axis=1)
    S = cache_arr.shape[1]
    oh = (jnp.arange(S) == pos)
    oh = oh.reshape((1, S) + (1,) * (cache_arr.ndim - 2))
    upd = jnp.where(oh, jnp.broadcast_to(new.astype(cache_arr.dtype), cache_arr.shape)
                    if new.shape[1] == 1 else new.astype(cache_arr.dtype), cache_arr)
    axes = ("batch", "act_kv") + (None,) * (cache_arr.ndim - 2)
    return shd.constrain_activation(upd, axes)


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
    scalar write position; ``positions`` is (B?, 1) the query position.
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
        cache_pos = jnp.asarray(cache_pos)
        ck = _cache_update(cache["k"], k, cache_pos)
        cv = _cache_update(cache["v"], v, cache_pos)
        k_pos = jnp.arange(ck.shape[1])
        if cache_pos.ndim == 1:
            # per-row positions: row b attends its OWN prefix k <= pos_b
            # (and its own window), so one fixed-shape decode batch can
            # hold sequences of different lengths — the continuous-
            # batching invariant that keeps recycled slots isolated
            qp = cache_pos[:, None]                           # (B, Sq=1)
            mask = k_pos[None, None, :] <= qp[:, :, None]     # (B, Sq, Sk)
            win = jnp.asarray(window, jnp.int32)
            win_m = k_pos[None, None, :] > qp[:, :, None] - win
            mask &= jnp.where(win > 0, win_m, True)
        else:
            k_valid = k_pos <= cache_pos
            # window relative to the *query* position (cache_pos), not k
            # order
            mask = attention_mask(
                jnp.broadcast_to(cache_pos[None], positions.shape),
                k_pos, causal=False, window=window, k_valid=k_valid,
            )
        out = _sdpa(q, ck, cv, mask, cfg.attn_softcap, kv_sharded=True)
        new_cache = {"k": ck, "v": cv}
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
    2*H*hd: the reason minicpm3 fits 32k cheaply.
    """
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape  # S == 1
    cache_pos = jnp.asarray(cache_pos)
    per_row = cache_pos.ndim == 1
    positions = (
        cache_pos[:, None] if per_row
        else jnp.full((S,), 0, jnp.int32) + cache_pos
    )
    c_new, kpe_new = _mla_latents(params, x, positions, cfg)
    c_kv = _cache_update(cache["c_kv"], c_new, cache_pos)
    k_pe = _cache_update(cache["k_pe"], kpe_new, cache_pos)
    q_nope, q_pe = _mla_queries(params, x, positions, cfg)
    q_c = jnp.einsum("bsnh,rnh->bsnr", q_nope, params["wuk"])
    scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_head_dim + m.qk_rope_head_dim))
    f32 = jnp.float32
    scores = (
        jnp.einsum("bsnr,btr->bnst", q_c, c_kv, preferred_element_type=f32)
        + jnp.einsum("bsnh,bth->bnst", q_pe, k_pe, preferred_element_type=f32)
    ) * scale
    k_pos = jnp.arange(c_kv.shape[1])
    if per_row:
        valid = k_pos[None, :] <= cache_pos[:, None]          # (B, T)
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    else:
        valid = k_pos <= cache_pos
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
    ctx = jnp.einsum("bnst,btr->bsnr", probs, c_kv)
    out = jnp.einsum("bsnr,rnh->bsnh", ctx, params["wuv"])
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return y, {"c_kv": c_kv, "k_pe": k_pe}


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    m: MLAConfig = cfg.mla
    return {
        "c_kv": ParamSpec((batch, max_len, m.kv_lora_rank), ("batch", "kv_seq", None), init="zeros"),
        "k_pe": ParamSpec((batch, max_len, m.qk_rope_head_dim), ("batch", "kv_seq", None), init="zeros"),
    }
