"""Required work of a decode step of a DeepSeek-V3 decoder (MLA + MoE),
counted from its configuration file alone (Hugging Face keys): the
operations and bytes a step needs, whatever code computes it.  Like
``bench/work.py`` it reads nothing of the program.

The configuration holds ``n_routed_experts`` experts per MoE layer (the
chip's share) of ``published.n_routed_experts`` that the router spans.
"""

from __future__ import annotations

from bench.work import BF16


def router_width(cfg: dict) -> int:
    return cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"])


def attention_params(cfg: dict) -> int:
    """MLA without query compression: W_q, W_kva, the latent norm, W_uk,
    W_uv, W_o."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (r + rope) + r + r * h * nope + r * h * v + h * v * d


def _mlp(d: int, f: int) -> int:
    return 3 * d * f


def moe_ffn_params(cfg: dict) -> tuple[int, int]:
    """(parameters every token uses, parameters of one held expert) of an
    MoE layer's FFN: router and correction bias and the shared experts;
    one routed expert."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e = router_width(cfg)
    return d * e + e + _mlp(d, cfg["n_shared_experts"] * f), _mlp(d, f)


def held_params(cfg: dict) -> int:
    """Every parameter the chip holds: embedding and head, every layer's
    attention and norms, the dense layers' MLPs, the MoE layers' router,
    bias, shared experts and held experts, and the final norm."""
    d, L, k = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    emb = cfg["vocab_size"] * d * (1 if cfg["tie_word_embeddings"] else 2)
    every, one = moe_ffn_params(cfg)
    layers = L * (attention_params(cfg) + 2 * d)
    layers += k * _mlp(d, cfg["intermediate_size"])
    layers += (L - k) * (every + cfg["n_routed_experts"] * one)
    return emb + layers + d


def latent_bytes_per_token(cfg: dict, itemsize: int = BF16) -> int:
    """Bytes of the latent cache (c_kv and k_pe) one position holds over
    all layers."""
    return cfg["num_hidden_layers"] * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def decode_work(cfg: dict, active: int, attended: int,
                itemsize: int = BF16) -> tuple[int, int]:
    """(flops, bytes) of one decode step with ``active`` sequences that
    attend ``attended`` cached positions in all.

    Bytes: every held parameter read once, except that the embedding reads
    only its ``active`` rows; the latent cache of every attended position
    read once and each new position's written.  Operations: two per weight
    per token for attention, the dense layers, the router, the shared
    experts and the head; for the held experts, the (token, expert) pairs
    the shapes expect, ``active`` x top-k x held / router width a layer;
    absorbed attention over the attended positions (scores against the
    latent and the rope key, the context from the latent)."""
    d, L, k = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    h, r, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    vd = cfg["vocab_size"] * d
    params = held_params(cfg)
    emb_unread = vd - active * d
    lat = latent_bytes_per_token(cfg, itemsize)
    nbytes = (params - emb_unread) * itemsize + (attended + active) * lat
    every, one = moe_ffn_params(cfg)
    dense = (L * attention_params(cfg) + k * _mlp(d, cfg["intermediate_size"])
             + (L - k) * every + vd)
    pairs = active * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_width(cfg)
    flops = (2 * active * dense + 2 * (L - k) * pairs * one
             + L * 2 * attended * h * (2 * r + rope))
    return int(flops), int(nbytes)
