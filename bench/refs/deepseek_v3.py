"""Plain reference of a DeepSeek-V3 decoder (Hugging Face ``model_type``
``deepseek_v3``, published config of ``moonshotai/Moonlight-16B-A3B``),
written from the published description and not from the code under test.

Embedding, then per layer x += o(MLA(rms(x))); x += FFN_l(rms(x)); final
RMSNorm and an untied output head.  RMSNorm has a learned scale.

- MLA without query compression (``q_lora_rank`` null): q = x W_q, per head
  ``qk_nope_head_dim`` + ``qk_rope_head_dim``; kv_a = x W_kva; the latent
  c = rms(kv_a[:kv_lora_rank]) (eps 1e-6, the norm's default, as the
  published code builds it); k_pe = kv_a[kv_lora_rank:], one for all heads;
  k_nope = c W_uk and v = c W_uv per head.  RoPE (theta ``rope_theta``, no
  scaling) on q_pe and k_pe, pairing as the published code does: the rope
  dims are de-interleaved (2i, 2i+1 -> i, i + rope/2), then rotated by
  halves.  Scores (q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope), causal,
  softmax in float32; the heads' outputs map back through W_o.
- FFN: the first ``first_k_dense_replace`` layers are a SwiGLU MLP of
  ``intermediate_size``.  The others: the shared experts, one SwiGLU MLP of
  ``n_shared_experts * moe_intermediate_size``, plus the routed sum.
  Routing per token in float32, no capacity: s = sigmoid(x W_g) over the
  router's experts, top ``num_experts_per_tok`` chosen on s + the
  correction bias (``noaux_tc``, one group), weights s[chosen], renormalised
  (``norm_topk_prob``) with 1e-20 in the denominator, times
  ``routed_scaling_factor``.

Departure from the published model: the held share.  The configuration
holds ``n_routed_experts`` experts of the router's width (the published
count), experts 0 .. n_routed_experts - 1, as one chip of an
expert-parallel deployment does; pairs routed to the others add nothing
here, as in the program.

It reads the benchmark's own weights, laid out as the served parameter tree
(``embed.table``; ``lead`` (dense) and ``layers`` (MoE) stacks of
``ln_attn``, ``attn.{wq,wdkv,kv_norm,wuk,wuv,wo}``, ``ln_mlp`` and
``mlp.{w_gate,w_up,w_down}`` or ``moe.{router,bias,w_gate,w_up,w_down}``
with ``dense_mlp``; ``final_norm``; ``unembed.table``), one layer at a time
in float32 with ``highest`` matmul precision, so that it fits beside them.

``control`` gives the control: the same forward with every weight matrix
rounded per output channel to fp8 (e4m3) first.  ``compare`` reads mean
gaps, not the largest (its docstring says why).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
CONTROL = False  # set by bench/controls/deepseek_v3.py
LATENT_EPS = 1e-6


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
    """x (B, S, H, r): de-interleave, then rotate by halves."""
    r = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (r // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)            # (r/2,)
    ang = pos[:, None].astype(F32) * inv[None, :]                  # (S, r/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + rot * sin


def _swiglu(h, m, control, lead=()):
    """SwiGLU of ``m`` (w_gate, w_up (d, f), w_down (f, d)), each already
    indexed by ``lead``."""
    wg, wu, wd = (_quant(m[n][lead].astype(F32), control, 0)
                  for n in ("w_gate", "w_up", "w_down"))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(h @ wg) * (h @ wu), wd)


def _attention(x, p, ln, cfg, control):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope = cfg["qk_rope_head_dim"]
    wq, wkva, wuk, wuv = (_quant(p[n].astype(F32), control, 0)
                          for n in ("wq", "wdkv", "wuk", "wuv"))
    wo = _quant(p["wo"].astype(F32), control, (0, 1))
    pos = jnp.arange(x.shape[1])
    h = _rms(x, ln.astype(F32), eps)
    q = jnp.einsum("bsd,dnh->bsnh", h, wq)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, theta)
    kv_a = h @ wkva
    c = _rms(kv_a[..., :r], p["kv_norm"]["scale"].astype(F32), LATENT_EPS)
    k_pe = _rope(kv_a[..., None, r:], pos, theta)[:, :, 0]          # (B, S, rope)
    k_nope = jnp.einsum("bsr,rnh->bsnh", c, wuk)
    v = jnp.einsum("bsr,rnh->bsnh", c, wuv)
    s = (jnp.einsum("bqnh,bknh->bnqk", q_nope, k_nope)
         + jnp.einsum("bqnh,bkh->bnqk", q_pe, k_pe)) / jnp.sqrt(F32(nope + rope))
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s, -jnp.inf)
    o = jnp.einsum("bnqk,bknh->bqnh", jax.nn.softmax(s, axis=-1), v)
    return x + jnp.einsum("bsnh,nhd->bsd", o, wo)


def _index(tree, l):
    return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, l, keepdims=False), tree)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _dense_layer(x, lead, l, cfg_items, control):
    cfg = dict(cfg_items)
    p = _index(lead, l)
    x = _attention(x, p["attn"], p["ln_attn"]["scale"], cfg, control)
    h = _rms(x, p["ln_mlp"]["scale"].astype(F32), cfg["rms_norm_eps"])
    return x + _swiglu(h, p["mlp"], control)


def routing(h, router, bias, cfg):
    """(B, S, E) float32 weights of each token over all experts: the
    chosen experts' weights, zero elsewhere."""
    s = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * w[..., None], axis=-2)


def moe_ffn(h, moe, shared, cfg, control=False):
    """The MoE layer's FFN on normed input h (B, S, d): the shared experts
    plus each held expert weighted by its routing weight."""
    w = routing(h, _quant(moe["router"].astype(F32), control, 0),
                moe["bias"].astype(F32), cfg)
    held = moe["w_gate"].shape[0]
    y = _swiglu(h, shared, control)
    for e in range(held):
        y = y + w[..., e, None] * _swiglu(h, moe, control, lead=e)
    return y


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _moe_layer(x, layers, l, cfg_items, control):
    cfg = dict(cfg_items)
    p = _index(layers, l)
    x = _attention(x, p["attn"], p["ln_attn"]["scale"], cfg, control)
    h = _rms(x, p["ln_mlp"]["scale"].astype(F32), cfg["rms_norm_eps"])
    return x + moe_ffn(h, p["moe"], p["dense_mlp"], cfg, control)


@functools.partial(jax.jit, static_argnames=("control",))
def _embed(table, tokens, control):
    return _quant(table.astype(F32), control, 1)[tokens]


def _logits(h, scale, table, eps, control):
    return _rms(h, scale.astype(F32), eps) @ _quant(table.astype(F32), control, 0)


@functools.partial(jax.jit, static_argnames=("eps", "k"))
def _served(h, scale, table, tok, eps, k):
    """Per row, the served token's distance below the reference's best
    logit, and below its k-th largest (zero within the top k)."""
    logits = _logits(h, scale, table, eps, False)
    top = jax.lax.top_k(logits, k)[0]
    at = jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]
    return top[:, 0] - at, jnp.maximum(top[:, -1] - at, 0.0)


@functools.partial(jax.jit, static_argnames=("eps", "k"))
def _chosen(h, hq, scale, table, eps, k):
    """Per row, as ``_served`` for the control's choices: its first token's
    distance below the reference's best logit, and the widest distance of
    its top k below the reference's k-th largest (any of them could be
    drawn)."""
    logits = _logits(h, scale, table, eps, False)
    top = jax.lax.top_k(logits, k)[0]
    ids = jax.lax.top_k(_logits(hq, scale, table, eps, True), k)[1]
    at = jnp.take_along_axis(logits, ids, 1)
    return top[:, 0] - at[:, 0], jnp.maximum(top[:, -1] - jnp.min(at, axis=1), 0.0)


def _check_share(params, cfg: dict) -> None:
    held = params["layers"]["moe"]["w_gate"].shape[1]
    if held != cfg["n_routed_experts"]:
        raise ValueError(f"the weights hold {held} experts, the configuration "
                         f"{cfg['n_routed_experts']}")


def hidden(params, cfg: dict, tokens: np.ndarray, control: bool = False) -> jnp.ndarray:
    """Final hidden states (B, S, d) of a causal forward over ``tokens``."""
    _check_share(params, cfg)
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    k = cfg["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["table"], jnp.asarray(tokens), control)
        for layer in range(cfg["num_hidden_layers"]):
            if layer < k:
                x = _dense_layer(x, params["lead"], jnp.int32(layer), items, control)
            else:
                x = _moe_layer(x, params["layers"], jnp.int32(layer - k), items, control)
    return x


def logits(params, cfg: dict, tokens: np.ndarray) -> jnp.ndarray:
    """(B, S, V) float32 logits of a causal forward over ``tokens``."""
    h = hidden(params, cfg, tokens)
    with jax.default_matmul_precision("highest"):
        return _logits(h, params["final_norm"]["scale"], params["unembed"]["table"],
                       cfg["rms_norm_eps"], False)


def served_rows(params, cfg: dict, seqs, pad_to: int, top_k: int,
                control: bool = False, block: int = 512) -> dict:
    """Per served position, in the order of ``seqs`` ((prompt, served
    tokens, greedy) per request): whether its request was greedy, and its
    gap, read at that position by the float32 forward.  A greedy token's gap
    is its distance below the reference's best logit; a sampled token's,
    its distance below the reference's ``top_k``-th logit (zero within the
    top ``top_k``, where the draw truncates).  With ``control`` the
    control's forward serves instead (``_chosen``): its first token where a
    request was greedy, its top ``top_k`` where it sampled."""
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
    n = len(rows)
    eps = cfg["rms_norm_eps"]
    table, scale = params["unembed"]["table"], params["final_norm"]["scale"]
    n_rows = -(-n // block) * block      # fixed block shapes: one compile
    idx = np.array(rows + [(0, 0)] * (n_rows - n))
    tok = np.array(served + [0] * (n_rows - n), np.int32)
    greedy = np.array(greedy, bool)
    h = hidden(params, cfg, toks)
    hq = hidden(params, cfg, toks, control=True) if control else None
    gaps = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, n_rows, block):
            sl = slice(i, i + block)
            sel = (jnp.asarray(idx[sl, 0]), jnp.asarray(idx[sl, 1]))
            if control:
                got = _chosen(h[sel], hq[sel], scale, table, eps, top_k)
            else:
                got = _served(h[sel], scale, table, jnp.asarray(tok[sl]), eps, top_k)
            gaps.append([np.asarray(a) for a in got])
    best_gap, kth_gap = (np.concatenate(g)[:n] for g in zip(*gaps))
    return {"greedy": greedy, "gap": np.where(greedy, best_gap, kth_gap)}


def compare(params, cfg: dict, seqs, pad_to: int, top_k: int,
            control: bool = False, block: int = 512) -> dict:
    """Mean gaps of the served tokens (``served_rows``): ``greedy_gap`` over
    the greedy requests' positions, ``topk_gap`` over the sampled ones'
    (zero where there are none).

    The mean and not the largest: in a bfloat16 program the routing of
    almost every token departs from the float32 reference's at some MoE
    layer, where scores nearly tie, and a departure at a held expert moves
    that token's logits by up to ~1.5.  So the largest gap reads the same
    for a sound bfloat16 program and an fp8 one, while the mean over
    thousands of positions reads ten times apart (PERF.md section 2)."""
    r = served_rows(params, cfg, seqs, pad_to, top_k, control, block)
    g = r["greedy"]
    mean = lambda where: float(r["gap"][where].mean()) if where.any() else 0.0
    return {"greedy_gap": mean(g), "topk_gap": mean(~g)}
