"""Required work counted from shapes: the operations and bytes a step needs,
whatever code computes it.  A roofline share divides the least time these
allow (the larger of operations over peak FLOP/s and bytes over peak
bandwidth) by a measured device time.  Nothing here reads the program, so a
change that removes work cannot move the yardstick.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

F32 = 4
I32 = 4
BF16 = 2


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, pk: dict) -> float:
    """The least time the chip could take for this work."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# -- LDA ---------------------------------------------------------------------


def lda_draw_work(tokens: int, M: int, V: int, K: int) -> tuple[int, int]:
    """(flops, bytes) of one z-draw over the corpus: each token reads its
    word's phi row (K f32) and its document's theta row once per document,
    forms K products and a running sum (2K operations), and reads its word id
    and writes its topic."""
    flops = 2 * K * tokens
    nbytes = tokens * K * F32 + M * K * F32 + tokens * (I32 + I32)
    return flops, nbytes


def lda_sweep_work(tokens: int, M: int, V: int, K: int) -> tuple[int, int]:
    """(flops, bytes) of one whole sweep: the draw, then theta (M, K) and phi
    (V, K) each written once by the Dirichlet updates, and the counts read
    back from the topics just written (one id per token)."""
    flops, nbytes = lda_draw_work(tokens, M, V, K)
    nbytes += (M * K + V * K) * F32 + tokens * I32
    return flops, nbytes


# -- decoder-only transformer (qwen3 layout) -----------------------------------


def qwen3_layer_params(cfg: dict) -> int:
    """Parameters of one block: q/k/v/o projections, the gated MLP and the
    three norm vectors (two RMSNorms and the per-head q/k norms)."""
    d, h, kv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * ff
    norms = 2 * d + 2 * hd
    return attn + mlp + norms


def qwen3_params(cfg: dict) -> int:
    """All parameters: blocks, the final norm and the (tied) embedding."""
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    if not cfg["tie_word_embeddings"]:
        emb *= 2
    return cfg["num_hidden_layers"] * qwen3_layer_params(cfg) + cfg["hidden_size"] + emb


def qwen3_kv_bytes_per_token(cfg: dict, itemsize: int = BF16) -> int:
    """Bytes of cached K and V that one position holds over all layers."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def qwen3_matmul_params(cfg: dict) -> int:
    """Parameters that multiply each token of a prefill (norms excluded)."""
    d, h, kv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    return cfg["num_hidden_layers"] * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff)


def qwen3_prefill_flops(cfg: dict, prompt_tokens: int) -> int:
    """Operations that prefilling an unpadded prompt of this many tokens
    requires into the cache: two per matmul parameter per token, plus causal
    attention's scores and weighted sum (2 x 2 x S(S+1)/2 x heads x head_dim
    per layer).  The prompt's logits are not needed."""
    S = prompt_tokens
    attn = 2 * 2 * (S * (S + 1) // 2) * cfg["num_attention_heads"] * cfg["head_dim"]
    return 2 * qwen3_matmul_params(cfg) * S + cfg["num_hidden_layers"] * attn


def qwen3_decode_work(cfg: dict, active: int, attended: int,
                      itemsize: int = BF16) -> tuple[int, int]:
    """(flops, bytes) of one decode step with ``active`` sequences that
    attend ``attended`` cached positions in all: every parameter read once,
    each attended position's K and V read once and each new position's
    written, two operations per matmul parameter per sequence, and the
    tied unembedding's logits."""
    params = qwen3_params(cfg)
    kvb = qwen3_kv_bytes_per_token(cfg, itemsize)
    nbytes = params * itemsize + attended * kvb + active * kvb
    hd, h = cfg["head_dim"], cfg["num_attention_heads"]
    flops = (2 * active * (qwen3_matmul_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"])
             + cfg["num_hidden_layers"] * 2 * 2 * attended * h * hd)
    return flops, nbytes


def vocab_draw_bytes(rows: int, vocab: int) -> int:
    """Bytes of one truncated draw per row over a (rows, vocab) f32 weight
    matrix: the weights read once and one index written per row."""
    return rows * vocab * F32 + rows * I32
