"""Plain reference of a Qwen3 decoder (Hugging Face ``Qwen3ForCausalLM``,
published config of ``Qwen/Qwen3-4B``), written from the published
description and not from the code under test.

Block: x += o(attn(rope(qknorm(q(rms(x)))), rope(qknorm(k(rms(x)))), v));
x += down(silu(gate(rms(x))) * up(rms(x))).  RMSNorm with a learned scale;
q/k norm is an RMSNorm over each head's 128 dims, before RoPE; RoPE rotates
the two halves of a head (``rotate_half``) with inverse frequencies
theta^(-2i/d); grouped-query attention shares each K/V head among
num_attention_heads / num_key_value_heads query heads; causal softmax in
float32 with scale 1/sqrt(head_dim); the output head is the tied embedding.

It reads the benchmark's own weights, laid out as the served parameter tree
(``embed.table``, ``layers.{ln_attn,attn.{wq,wk,wv,wo,q_norm,k_norm},ln_mlp,
mlp.{w_gate,w_up,w_down}}``, ``final_norm``), one layer at a time in
float32 with ``highest`` matmul precision, so that it fits beside them.

``control`` gives the control: the same forward with every weight matrix
rounded per output channel to fp8 (e4m3) first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
CONTROL = False  # set by bench/controls/qwen3.py


def _quant(w, control, axis):
    """With ``control``, ``w`` rounded per output channel (max over
    ``axis``, the contracted axes) to fp8 e4m3, back in float32."""
    if not control:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 240.0   # e4m3 without reserved codes
    return jax.lax.reduce_precision(w / s, exponent_bits=4, mantissa_bits=3) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)          # (hd/2,)
    ang = pos[:, None].astype(F32) * inv[None, :]                  # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(x, layers, l, cfg_items, control):
    cfg = dict(cfg_items)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    g = lambda t: jax.lax.dynamic_index_in_dim(t, l, keepdims=False).astype(F32)
    a, m = layers["attn"], layers["mlp"]
    wq, wk, wv = (_quant(g(a[n]), control, 0) for n in ("wq", "wk", "wv"))
    wo = _quant(g(a["wo"]), control, (0, 1))
    B, S, _ = x.shape
    pos = jnp.arange(S)
    h = _rms(x, g(layers["ln_attn"]["scale"]), eps)
    q = jnp.einsum("bsd,dnh->bsnh", h, wq)
    k = jnp.einsum("bsd,dnh->bsnh", h, wk)
    v = jnp.einsum("bsd,dnh->bsnh", h, wv)
    q = _rope(_rms(q, g(a["q_norm"]["scale"]), eps), pos, theta)
    k = _rope(_rms(k, g(a["k_norm"]["scale"]), eps), pos, theta)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqnh,bknh->bnqk", q, k) / jnp.sqrt(F32(hd))
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnqk,bknh->bqnh", p, v)
    x = x + jnp.einsum("bsnh,nhd->bsd", o, wo)
    h = _rms(x, g(layers["ln_mlp"]["scale"]), eps)
    wg, wu = (_quant(g(m[n]), control, 0) for n in ("w_gate", "w_up"))
    wd = _quant(g(m["w_down"]), control, 0)
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(h @ wg) * (h @ wu), wd)


@functools.partial(jax.jit, static_argnames=("control",))
def _embed(table, tokens, control):
    return _quant(table.astype(F32), control, 1)[tokens]


def _logits(h, scale, table, eps, control):
    return _rms(h, scale.astype(F32), eps) @ _quant(table.astype(F32), control, 1).T


@functools.partial(jax.jit, static_argnames=("eps", "k"))
def _served(h, scale, table, tok, eps, k):
    """Per row: the reference's best logit, its k-th largest, and the served
    token's."""
    logits = _logits(h, scale, table, eps, False)
    top = jax.lax.top_k(logits, k)[0]
    return top[:, 0], top[:, -1], jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnames=("eps", "k"))
def _chosen(h, hq, scale, table, eps, k):
    """Per row: the reference's best and k-th largest logits, its logit of
    the control's first token, and its least logit among the control's top
    k."""
    logits = _logits(h, scale, table, eps, False)
    top = jax.lax.top_k(logits, k)[0]
    ids = jax.lax.top_k(_logits(hq, scale, table, eps, True), k)[1]
    at = jnp.take_along_axis(logits, ids, 1)
    return top[:, 0], top[:, -1], at[:, 0], jnp.min(at, axis=1)


def hidden(params, cfg: dict, tokens: np.ndarray, control: bool = False) -> jnp.ndarray:
    """Final hidden states (B, S, d) of a causal forward over ``tokens``."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["table"], jnp.asarray(tokens), control)
        for layer in range(cfg["num_hidden_layers"]):
            x = _layer(x, params["layers"], jnp.int32(layer), items, control)
    return x


def compare(params, cfg: dict, seqs, pad_to: int, top_k: int,
            control: bool = False, block: int = 512) -> dict:
    """Widest gaps of served tokens below what the reference would allow.

    ``seqs``: (prompt, served tokens, greedy) per request.  At each served
    position the float32 forward gives the reference's logits.
    ``greedy_gap``: the largest distance of a greedy request's token below
    the reference's best.  ``topk_gap``: the largest distance of a sampled
    token below the reference's ``top_k``-th logit (the draw truncates to
    its top ``top_k``, or fewer).  With ``control`` the control's forward picks
    the tokens instead: its first token where a request was greedy, its top
    ``top_k`` where it sampled, each judged against the float32 reference.
    """
    B = len(seqs)
    toks = np.zeros((B, pad_to), np.int32)
    rows, served, greedy = [], [], []
    for b, (prompt, out, g) in enumerate(seqs):
        full = np.concatenate([prompt, out]).astype(np.int32)[:pad_to]
        toks[b, :len(full)] = full
        n = len(prompt)
        for j in range(len(out)):
            rows.append((b, n - 1 + j))
            served.append(int(out[j]))
            greedy.append(bool(g))
    eps = cfg["rms_norm_eps"]
    table, scale = params["embed"]["table"], params["final_norm"]["scale"]
    n_rows = -(-len(rows) // block) * block      # fixed block shapes: one compile
    pad = n_rows - len(rows)
    idx = np.array(rows + [(0, 0)] * pad)
    served += [0] * pad
    valid = np.arange(n_rows) < len(rows)
    greedy = np.array(greedy + [False] * pad)
    sampled = valid & ~greedy
    gaps_greedy, gaps_topk = [0.0], [0.0]
    h = hidden(params, cfg, toks)
    hq = hidden(params, cfg, toks, control=True) if control else None
    tok = np.array(served, np.int32)
    with jax.default_matmul_precision("highest"):
        for i in range(0, n_rows, block):
            sl = slice(i, i + block)
            sel = (jnp.asarray(idx[sl, 0]), jnp.asarray(idx[sl, 1]))
            g, smp = greedy[sl], sampled[sl]
            if not control:
                got = _served(h[sel], scale, table, jnp.asarray(tok[sl]), eps, top_k)
                best, kth, first = (np.asarray(a) for a in got)
                worst = first
            else:
                got = _chosen(h[sel], hq[sel], scale, table, eps, top_k)
                best, kth, first, worst = (np.asarray(a) for a in got)
            gaps_greedy.append(float(np.max(best - first, where=g, initial=0.0)))
            gaps_topk.append(float(np.max(kth - worst, where=smp, initial=0.0)))
    return {"greedy_gap": max(gaps_greedy), "topk_gap": max(gaps_topk)}
