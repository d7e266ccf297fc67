"""Decoder-only LM stack: one scan-over-layers serving four layer families
(dense / moe / ssm / hybrid) in three modes (full, prefill, decode).

Scan keeps the HLO a single layer wide — compile times at 512 devices stay
flat in depth — and params/caches are stacked (L, ...) pytrees, which is
also the checkpoint layout.  Per-layer attention windows are data (an
int32 xs vector), not structure, so gemma2's local/global alternation and
hymba's 3-full-attention pattern don't change the traced graph.  An MoE
model's leading dense layers (``first_k_dense``) are a stack of their own,
``lead``, scanned before ``layers``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    embed,
    embedding_spec,
    mlp,
    mlp_spec,
    rmsnorm,
    rmsnorm_spec,
    unembed,
    unembed_spec,
)
from repro.models.params import ParamSpec, stack_specs_tree


# ---------------------------------------------------------------------------
# per-layer spec
# ---------------------------------------------------------------------------


def layer_spec(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    spec: Dict = {}
    if cfg.family in ("dense", "moe", "hybrid", "vlm", "audio"):
        spec["ln_attn"] = rmsnorm_spec(d)
        spec["attn"] = attn.mla_spec(cfg) if cfg.attention == "mla" else attn.gqa_spec(cfg)
        if cfg.post_norms:
            spec["ln_post_attn"] = rmsnorm_spec(d)
    if cfg.family in ("dense", "vlm", "audio", "hybrid"):
        spec["ln_mlp"] = rmsnorm_spec(d)
        spec["mlp"] = mlp_spec(d, cfg.d_ff)
        if cfg.post_norms:
            spec["ln_post_mlp"] = rmsnorm_spec(d)
    if cfg.family == "moe":
        spec["ln_mlp"] = rmsnorm_spec(d)
        spec["moe"] = moe_mod.moe_spec(cfg)
        if cfg.moe.dense_residual_d_ff > 0:
            spec["dense_mlp"] = mlp_spec(d, cfg.moe.dense_residual_d_ff)
    if cfg.family in ("ssm", "hybrid"):
        key = "ssm"
        if cfg.family == "ssm":
            spec["ln_ssm"] = rmsnorm_spec(d)
        spec[key] = ssm_mod.ssm_spec(cfg)
        if cfg.family == "hybrid":
            # learned per-branch output scales (hymba's beta_attn/beta_ssm)
            spec["branch_scale"] = ParamSpec((2,), (None,), init="ones")
    return spec


def layer_cache_spec(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    spec: Dict = {}
    if cfg.family in ("dense", "moe", "hybrid", "vlm", "audio"):
        if cfg.attention == "mla":
            spec["attn"] = attn.mla_cache_spec(cfg, batch, max_len)
        else:
            spec["attn"] = attn.gqa_cache_spec(cfg, batch, max_len)
    if cfg.family in ("ssm", "hybrid"):
        spec["ssm"] = ssm_mod.ssm_cache_spec(cfg, batch)
    return spec


# ---------------------------------------------------------------------------
# per-layer forward
# ---------------------------------------------------------------------------


def _attn_branch(p, h, positions, window, cfg, cache, cache_pos):
    if cfg.attention == "mla":
        with jax.named_scope("mla"):
            if cache is None:
                return attn.mla_attend_full(p, h, positions, cfg)
            return attn.mla_attend_decode(p, h, cache, cache_pos, cfg)
    if cache is None:
        y, kv = attn.gqa_attend(p, h, positions, cfg, causal=True, window=window)
        return y, ({"k": kv[0], "v": kv[1]} if kv is not None else None)
    y, c = attn.gqa_attend(
        p, h, positions, cfg, causal=False, window=window, cache=cache, cache_pos=cache_pos
    )
    return y, c


def layer_apply(
    cfg: ModelConfig,
    p: Dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    window: jnp.ndarray,
    cache: Optional[Dict] = None,
    cache_pos: Optional[jnp.ndarray] = None,
    serve: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """One block.  Returns (x, cache_out, aux_loss).  ``serve`` (prefill
    and decode) routes MoE layers drop-free (``moe.moe_held``)."""
    aux = jnp.float32(0.0)
    cache_out: Dict = {}
    attn_cache = None if cache is None else cache.get("attn")
    ssm_cache = None if cache is None else cache.get("ssm")

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        h = rmsnorm(p["ln_attn"], x, cfg.norm_eps)
        y, c = _attn_branch(p["attn"], h, positions, window, cfg, attn_cache, cache_pos)
        if cfg.post_norms:
            y = rmsnorm(p["ln_post_attn"], y, cfg.norm_eps)
        x = x + y
        if c is not None:
            cache_out["attn"] = c
        h = rmsnorm(p["ln_mlp"], x, cfg.norm_eps)
        if cfg.family == "moe":
            if serve or cfg.moe.capacity_factor <= 0:
                y = moe_mod.moe_held(p["moe"], h, cfg)
            else:
                y, aux_l = moe_mod.moe_block(p["moe"], h, cfg, cfg.moe_dispatch)
                aux = aux + aux_l
            if cfg.moe.dense_residual_d_ff > 0:
                with jax.named_scope("moe.shared"):
                    y = y + mlp(p["dense_mlp"], h, cfg.act)
        else:
            y = mlp(p["mlp"], h, cfg.act)
        if cfg.post_norms:
            y = rmsnorm(p["ln_post_mlp"], y, cfg.norm_eps)
        x = x + y

    elif cfg.family == "ssm":
        h = rmsnorm(p["ln_ssm"], x, cfg.norm_eps)
        if ssm_cache is None:
            y, c = ssm_mod.ssm_block(p["ssm"], h, cfg)
        else:
            y, c = ssm_mod.ssm_decode_step(p["ssm"], h, ssm_cache, cfg)
        x = x + y
        cache_out["ssm"] = c

    elif cfg.family == "hybrid":
        h = rmsnorm(p["ln_attn"], x, cfg.norm_eps)
        ya, ca = _attn_branch(p["attn"], h, positions, window, cfg, attn_cache, cache_pos)
        if ssm_cache is None:
            ys, cs = ssm_mod.ssm_block(p["ssm"], h, cfg)
        else:
            ys, cs = ssm_mod.ssm_decode_step(p["ssm"], h, ssm_cache, cfg)
        bs = p["branch_scale"].astype(jnp.float32)
        x = x + (bs[0] * ya.astype(jnp.float32) + bs[1] * ys.astype(jnp.float32)).astype(x.dtype)
        if ca is not None:
            cache_out["attn"] = ca
        cache_out["ssm"] = cs
        h = rmsnorm(p["ln_mlp"], x, cfg.norm_eps)
        x = x + mlp(p["mlp"], h, cfg.act)
    else:
        raise ValueError(cfg.family)

    return x, (cache_out or None), aux


# ---------------------------------------------------------------------------
# layer windows (static pattern -> data vector)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig) -> np.ndarray:
    L = cfg.num_layers
    w = np.zeros((L,), np.int32)
    if cfg.layer_pattern == "local_global" and cfg.sliding_window > 0:
        w[0::2] = cfg.sliding_window  # even layers local (gemma2)
    elif cfg.family == "hybrid" and cfg.local_window > 0:
        w[:] = cfg.local_window
        for full in (0, L // 2, L - 1):  # hymba's 3 full-attention layers
            w[full] = 0
    return w


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------


def stacks(cfg: ModelConfig) -> Tuple[Tuple[str, ModelConfig], ...]:
    """(params key, config) of each scanned stack, in order: the leading
    dense layers as ``lead`` (when ``first_k_dense``), the rest as
    ``layers``."""
    k = cfg.first_k_dense
    if k == 0:
        return (("layers", cfg),)
    lead = dataclasses.replace(cfg, family="dense", moe=None, num_layers=k, first_k_dense=0)
    rest = dataclasses.replace(cfg, num_layers=cfg.num_layers - k, first_k_dense=0)
    return (("lead", lead), ("layers", rest))


def stack_specs(cfg: ModelConfig) -> Dict:
    return stack_specs_tree(layer_spec(cfg), cfg.num_layers)


def _stored(cfg: ModelConfig, per_stack: Dict) -> Dict:
    """Caches as the engine holds them, from one tree per stack: keyed by
    stack with leading dense layers, else the one stack's tree itself."""
    return per_stack if cfg.first_k_dense else per_stack["layers"]


def stack_cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Stacked (L, B, S, ...) caches (``_stored``)."""
    return _stored(cfg, {
        key: stack_specs_tree(layer_cache_spec(c, batch, max_len), c.num_layers)
        for key, c in stacks(cfg)
    })


def stack_apply(
    cfg: ModelConfig,
    params: Dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    caches: Optional[Dict] = None,
    cache_pos: Optional[jnp.ndarray] = None,
    collect_cache: bool = False,
    remat: str = "full",
):
    """Scan the layer stack.  Returns (x, caches_out, aux_total)."""
    windows = jnp.asarray(layer_windows(cfg))

    from repro.dist.sharding import constrain_activation

    def body(carry, xs):
        x, aux = carry
        if caches is not None:
            lp, w, lcache = xs
        else:
            lp, w = xs
            lcache = None
        if x.shape[1] > 1:  # not decode: allow seq-sharded saved carries
            x = constrain_activation(x, ("batch", "act_seq", None))
        x, cache_out, aux_l = layer_apply(
            cfg, lp, x, positions, w, cache=lcache, cache_pos=cache_pos,
            serve=collect_cache or caches is not None,
        )
        ys = cache_out if (collect_cache or caches is not None) else None
        return (x, aux + aux_l), ys

    if remat == "full":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )

    xs = (params, windows) if caches is None else (params, windows, caches)
    (x, aux), caches_out = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
    if caches is not None and attn.rows_in_place():
        # the scan read the caches and gave back only the step's rows
        caches_out = jax.tree_util.tree_map_with_path(
            lambda path, c, new: (attn.write_rows(c, new, cache_pos)
                                  if attn.is_seq_leaf(path) else new),
            caches, caches_out)
    return x, caches_out, aux


def lm_stacks(cfg, params, x, positions, caches=None, cache_pos=None,
              collect_cache=False, remat="full"):
    """``stack_apply`` over each of ``stacks(cfg)`` in turn."""
    if caches is not None and not cfg.first_k_dense:
        caches = {"layers": caches}
    outs, auxs = {}, []
    for key, c in stacks(cfg):
        x, outs[key], a = stack_apply(
            c, params[key], x, positions, None if caches is None else caches[key],
            cache_pos, collect_cache, remat)
        auxs.append(a)
    caches_out = _stored(cfg, outs) if collect_cache or caches is not None else None
    return x, caches_out, sum(auxs[1:], auxs[0])


# ---------------------------------------------------------------------------
# LM heads: specs + three entry points
# ---------------------------------------------------------------------------


def lm_specs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    spec = {"embed": embedding_spec(cfg.padded_vocab, d), "final_norm": rmsnorm_spec(d)}
    for key, c in stacks(cfg):
        spec[key] = stack_specs(c)
    if not cfg.tie_embeddings:
        spec["unembed"] = unembed_spec(cfg.padded_vocab, d)
    if cfg.meta_tokens > 0:
        spec["meta"] = ParamSpec((cfg.meta_tokens, d), (None, "embed"), scale=0.02)
    if cfg.frontend_len > 0:
        # stub frontend projection: precomputed embeddings -> d_model
        spec["frontend_proj"] = ParamSpec((d, d), ("embed", "embed_out"))
    return spec


def _input_embeddings(cfg, params, tokens, frontend_embeds=None):
    """tokens (B, S_text); frontend_embeds (B, S_front, D) or None.
    Returns (B, S_total, D) with meta tokens / frontend prepended."""
    x = embed(params["embed"], tokens, scale=cfg.embedding_scale)
    parts = []
    if cfg.meta_tokens > 0:
        B = tokens.shape[0]
        meta = jnp.broadcast_to(
            params["meta"].astype(x.dtype)[None], (B, cfg.meta_tokens, x.shape[-1])
        )
        parts.append(meta)
    if frontend_embeds is not None:
        fe = jnp.einsum("bsd,de->bse", frontend_embeds.astype(x.dtype), params["frontend_proj"])
        parts.append(fe)
    parts.append(x)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x


def _logits(cfg, params, x):
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    logits = unembed(
        params.get("unembed"), h, tied_table=tied, softcap=cfg.final_softcap
    )
    if cfg.padded_vocab != cfg.vocab_size:
        # mask padded columns: elementwise on the (sharded) vocab dim, so
        # loss and sampling see exactly the real vocabulary
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))
    return logits


def lm_apply(
    cfg: ModelConfig,
    params: Dict,
    tokens: jnp.ndarray,
    frontend_embeds: Optional[jnp.ndarray] = None,
    remat: str = "full",
) -> jnp.ndarray:
    """Training forward: logits for every *text* position (B, S_text, V)."""
    x = _input_embeddings(cfg, params, tokens, frontend_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)
    x, _, aux = lm_stacks(cfg, params, x, positions, remat=remat)
    prefix = cfg.meta_tokens + (frontend_embeds.shape[1] if frontend_embeds is not None else 0)
    if prefix > 0:
        x = x[:, prefix:]
    return _logits(cfg, params, x), aux


def lm_prefill(
    cfg: ModelConfig,
    params: Dict,
    tokens: jnp.ndarray,
    frontend_embeds: Optional[jnp.ndarray] = None,
    remat: str = "none",
):
    """Prefill: returns (last-position logits (B, V), stacked caches)."""
    x = _input_embeddings(cfg, params, tokens, frontend_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)
    x, caches, _ = lm_stacks(cfg, params, x, positions, collect_cache=True, remat=remat)
    return _logits(cfg, params, x[:, -1:, :])[:, 0, :], caches


def lm_decode(
    cfg: ModelConfig,
    params: Dict,
    caches: Dict,
    tokens: jnp.ndarray,      # (B, 1) current tokens
    cache_pos: jnp.ndarray,   # scalar int32, or (B,) per-row positions
):
    """One decode step.  Returns (logits (B, V), new caches).

    ``cache_pos`` is a scalar write position shared by the batch, or a
    (B,) vector of per-row positions — the continuous-batching form,
    where every slot of one fixed-shape decode batch sits at its own
    sequence length (repro.serve.batching)."""
    x = embed(params["embed"], tokens, scale=cfg.embedding_scale)
    cache_pos = jnp.asarray(cache_pos)
    positions = cache_pos[None] if cache_pos.ndim == 0 else cache_pos
    if cache_pos.ndim == 1:
        positions = cache_pos[:, None]    # (B, S=1) per-row RoPE positions
    x, caches_out, _ = lm_stacks(
        cfg, params, x, positions, caches=caches, cache_pos=cache_pos, remat="none"
    )
    return _logits(cfg, params, x[:, -1:, :])[:, 0, :], caches_out
