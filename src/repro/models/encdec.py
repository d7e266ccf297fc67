"""Encoder-decoder backbone (seamless-m4t): bidirectional encoder over
frontend embeddings (audio stub) + causal decoder with cross-attention.

Caches: decoder self-attention KV (grows during decode) + per-layer cross
KV precomputed once from the encoder memory (static during decode).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import embed, embedding_spec, mlp, mlp_spec, rmsnorm, rmsnorm_spec, unembed, unembed_spec
from repro.models.params import ParamSpec, stack_specs_tree


def _enc_layer_spec(cfg: ModelConfig) -> Dict:
    return {
        "ln_attn": rmsnorm_spec(cfg.d_model),
        "attn": attn.gqa_spec(cfg),
        "ln_mlp": rmsnorm_spec(cfg.d_model),
        "mlp": mlp_spec(cfg.d_model, cfg.d_ff),
    }


def _dec_layer_spec(cfg: ModelConfig) -> Dict:
    return {
        "ln_self": rmsnorm_spec(cfg.d_model),
        "self_attn": attn.gqa_spec(cfg),
        "ln_cross": rmsnorm_spec(cfg.d_model),
        "cross_attn": attn.cross_attention_spec(cfg),
        "ln_mlp": rmsnorm_spec(cfg.d_model),
        "mlp": mlp_spec(cfg.d_model, cfg.d_ff),
    }


def encdec_specs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    return {
        "frontend_proj": ParamSpec((d, d), ("embed", "embed_out")),
        "encoder": stack_specs_tree(_enc_layer_spec(cfg), cfg.encoder_layers),
        "enc_norm": rmsnorm_spec(d),
        "embed": embedding_spec(cfg.padded_vocab, d),
        "decoder": stack_specs_tree(_dec_layer_spec(cfg), cfg.num_layers),
        "final_norm": rmsnorm_spec(d),
        "unembed": unembed_spec(cfg.padded_vocab, d),
    }


def _masked_unembed(cfg: ModelConfig, params, h):
    logits = unembed(params["unembed"], h)
    if cfg.padded_vocab != cfg.vocab_size:
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))
    return logits


def encode(cfg: ModelConfig, params: Dict, src_embeds: jnp.ndarray, remat: str = "full"):
    """src_embeds (B, Se, D) from the stub audio frontend -> memory (B, Se, D)."""
    x = jnp.einsum("bsd,de->bse", src_embeds, params["frontend_proj"])
    positions = jnp.arange(x.shape[1])

    def body(carry, lp):
        x = carry
        h = rmsnorm(lp["ln_attn"], x, cfg.norm_eps)
        y, _ = attn.gqa_attend(lp["attn"], h, positions, cfg, causal=False)
        x = x + y
        h = rmsnorm(lp["ln_mlp"], x, cfg.norm_eps)
        x = x + mlp(lp["mlp"], h, cfg.act)
        return x, None

    if remat == "full":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _decoder_stack(cfg, params, x, positions, memory, caches=None, cache_pos=None,
                   collect_cache=False, remat="full"):
    in_place = attn.rows_in_place()

    def body(carry, xs):
        x = carry
        if caches is not None:
            lp, lcache = xs
        else:
            lp = xs
            lcache = None
        h = rmsnorm(lp["ln_self"], x, cfg.norm_eps)
        if lcache is None:
            y, kv = attn.gqa_attend(lp["self_attn"], h, positions, cfg, causal=True)
            self_cache = {"k": kv[0], "v": kv[1]}
            cross_kv = attn.cross_memory(lp["cross_attn"], memory, cfg)
        else:
            y, self_cache = attn.gqa_attend(
                lp["self_attn"], h, positions, cfg, causal=False,
                cache={"k": lcache["self_k"], "v": lcache["self_v"]},
                cache_pos=cache_pos,
            )
            cross_kv = (lcache["cross_k"], lcache["cross_v"])
        x = x + y
        h = rmsnorm(lp["ln_cross"], x, cfg.norm_eps)
        x = x + attn.cross_attend(lp["cross_attn"], h, cross_kv, cfg)
        h = rmsnorm(lp["ln_mlp"], x, cfg.norm_eps)
        x = x + mlp(lp["mlp"], h, cfg.act)
        cache_out = None
        if collect_cache or caches is not None:
            cache_out = {"self_k": self_cache["k"], "self_v": self_cache["v"]}
            if caches is None or not in_place:
                cache_out.update(cross_k=cross_kv[0], cross_v=cross_kv[1])
        return x, cache_out

    if remat == "full":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    xs = params["decoder"] if caches is None else (params["decoder"], caches)
    x, caches_out = jax.lax.scan(body, x, xs)
    if caches is not None and in_place:
        # the scan read the caches and gave back only the step's self rows
        caches_out = dict(
            caches,
            self_k=attn.write_rows(caches["self_k"], caches_out["self_k"], cache_pos),
            self_v=attn.write_rows(caches["self_v"], caches_out["self_v"], cache_pos),
        )
    return x, caches_out


def encdec_apply(cfg: ModelConfig, params: Dict, src_embeds, tgt_tokens, remat="full"):
    """Training forward: (B,Se,D) x (B,St) -> logits (B,St,V), aux=0."""
    memory = encode(cfg, params, src_embeds, remat=remat)
    x = embed(params["embed"], tgt_tokens)
    positions = jnp.arange(x.shape[1])
    x, _ = _decoder_stack(cfg, params, x, positions, memory, remat=remat)
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _masked_unembed(cfg, params, h), jnp.float32(0.0)


def encdec_prefill(cfg: ModelConfig, params: Dict, src_embeds, tgt_tokens, remat="none"):
    """Returns (last-position logits, stacked decode caches)."""
    memory = encode(cfg, params, src_embeds, remat=remat)
    x = embed(params["embed"], tgt_tokens)
    positions = jnp.arange(x.shape[1])
    x, caches = _decoder_stack(
        cfg, params, x, positions, memory, collect_cache=True, remat=remat
    )
    h = rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    return _masked_unembed(cfg, params, h)[:, 0, :], caches


def encdec_decode(cfg: ModelConfig, params: Dict, caches, tokens, cache_pos):
    """One decode step against self KV + precomputed cross KV caches."""
    x = embed(params["embed"], tokens)
    # (1,) shared, or (B, 1) per row as ``lm_decode`` gives RoPE
    positions = cache_pos[None] if cache_pos.ndim == 0 else cache_pos[:, None]
    x, caches_out = _decoder_stack(
        cfg, params, x, positions, None, caches=caches, cache_pos=cache_pos,
        remat="none",
    )
    h = rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    return _masked_unembed(cfg, params, h)[:, 0, :], caches_out


def encdec_cache_specs(cfg: ModelConfig, batch: int, tgt_len: int, src_len: int) -> Dict:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    layer = {
        "self_k": ParamSpec((batch, tgt_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head"), init="zeros"),
        "self_v": ParamSpec((batch, tgt_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head"), init="zeros"),
        "cross_k": ParamSpec((batch, src_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head"), init="zeros"),
        "cross_v": ParamSpec((batch, src_len, kv, hd), ("batch", "kv_seq", "kv_heads", "head"), init="zeros"),
    }
    return stack_specs_tree(layer, cfg.num_layers)
