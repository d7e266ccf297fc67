"""Mixture-of-Experts FFN: grouped top-k routing with capacity, two
dispatch modes.

Tokens are routed in *groups* of ``group_tokens`` (Switch/GShard style):
capacity C = ceil(cf * group * k / E) is per group, so dispatch/combine
intermediates scale as O(T * group * k * cf) — bounded in sequence length
(a global capacity would make the one-hots quadratic in T; that exact bug
is what §Perf iteration 0 of EXPERIMENTS.md documents).

``einsum`` (baseline, GShard/MaxText classic): one-hot dispatch/combine
tensors contracted with dense einsums.  Robustly partitioned by GSPMD but
the one-hot contractions are *fake FLOPs* in cost_analysis — visible in
the MODEL_FLOPS/HLO_FLOPs ratio (EXPERIMENTS.md §Roofline).

``gather`` (beyond-paper optimization, §Perf): position-in-expert via the
same cumsum, then scatter-add dispatch / gather combine.  Identical
semantics (same capacity dropping, same priority), no fake FLOPs.

``moe_held`` (serving, and training where ``capacity_factor`` is 0):
drop-free.  Every token is routed over all ``num_experts`` with no
capacity, and the layer computes the part of the result that the experts
it holds give (expert parallelism: experts 0 .. ``num_held`` - 1; the
exchange with the chips that hold the rest is not part of it).  Each held
expert's weights are read once and run on every token, with weight zero
where the token did not choose it, so each row's output depends on that
row alone.

Routing is deterministic top-k — NOT sampling; the paper's butterfly
sampler is deliberately not used here (DESIGN.md §4).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import _act
from repro.models.params import ParamSpec


def moe_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    """The router over all ``num_experts`` and the weights of the held
    experts only."""
    m: MoEConfig = cfg.moe
    d, e, f = cfg.d_model, m.held, m.expert_d_ff
    spec = {
        "router": ParamSpec((d, m.num_experts), ("embed", None), scale=0.02),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
        "w_down": ParamSpec((e, f, d), ("experts", "mlp", "embed")),
    }
    if m.score_bias:
        spec["bias"] = ParamSpec((m.num_experts,), (None,), scale=0.02)
    return spec


def _group(T: int, m: MoEConfig) -> Tuple[int, int]:
    g = min(m.group_tokens, T)
    while T % g:
        g //= 2
    return T // g, g


def _capacity(g: int, m: MoEConfig) -> int:
    return max(int(np.ceil(m.capacity_factor * g * m.top_k / m.num_experts)), 1)


def route(params, x, m: MoEConfig):
    """The router over all ``num_experts``, float32, x (..., D) ->
    (logits, scores (..., E), ids, gates (..., k)).  Scores are the softmax
    or sigmoid of the logits; the top ``top_k`` are chosen on score (+ the
    per-expert bias when ``score_bias``); their gates are the unbiased
    scores, renormalised, times ``routed_scaling``.  Every dispatch routes
    through it."""
    logits = jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), params["router"].astype(jnp.float32)
    )
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + params["bias"].astype(jnp.float32) if m.score_bias else scores
    _, ids = jax.lax.top_k(choice, m.top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) * m.routed_scaling
    return logits, scores, ids, gates


def _route(params, xg, m: MoEConfig):
    """xg (G, g, D) -> gates (G, g, k), ids (G, g, k), aux loss (scalar):
    ``route`` and the load-balance and z losses."""
    logits, scores, ids, gates = route(params, xg, m)
    probs = scores if m.scoring == "softmax" else scores / scores.sum(-1, keepdims=True)
    E = probs.shape[-1]
    assign1 = jax.nn.one_hot(ids[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(assign1.mean((0, 1)) * probs.mean((0, 1)))
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates, ids, aux + m.router_z_loss * zloss


def _positions(ids, E: int, k: int):
    """Rank of each (token, choice) within its expert, per group.
    ids (G, g, k) -> pos (G, g, k) fp32, assign (G, g, k, E) fp32."""
    G, g, _ = ids.shape
    assign = jax.nn.one_hot(ids, E, dtype=jnp.float32)            # (G,g,k,E)
    flat = assign.reshape(G, g * k, E)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(G, g, k, E)
    pos = jnp.sum(pos * assign, axis=-1)                          # (G,g,k)
    return pos, assign


def _expert_ffn(params, xd, act: str):
    """xd (E, N, D) -> (E, N, D)."""
    gate = _act(act)(jnp.einsum("end,edf->enf", xd, params["w_gate"]))
    up = jnp.einsum("end,edf->enf", xd, params["w_up"])
    return jnp.einsum("enf,efd->end", gate * up, params["w_down"])


def _moe_einsum(params, xg, m: MoEConfig, act: str):
    """GShard-style one-hot dispatch (baseline).  xg (G, g, D)."""
    G, g, D = xg.shape
    E, k, C = m.num_experts, m.top_k, _capacity(g, m)
    gates, ids, aux = _route(params, xg, m)
    pos, assign = _positions(ids, E, k)
    keep = (pos < C).astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32) * keep[..., None]
    dispatch = jnp.einsum("Gtke,Gtkc->Gtec", assign, pos_oh)        # (G,g,E,C)
    # combine weights each slot (e, c) by the gate of the (t, k) claiming it
    combine = jnp.einsum("Gtke,Gtkc,Gtk->Gtec", assign, pos_oh, gates)
    xd = jnp.einsum("Gtd,Gtec->Gecd", xg.astype(jnp.float32), dispatch)
    out = _expert_ffn(params, xd.reshape(G, E, C, D).transpose(1, 0, 2, 3).reshape(E, G * C, D).astype(xg.dtype), act)
    out = out.reshape(E, G, C, D).transpose(1, 0, 2, 3)             # (G,E,C,D)
    y = jnp.einsum("Gecd,Gtec->Gtd", out.astype(jnp.float32), combine)
    return y.astype(xg.dtype), aux


def _moe_gather(params, xg, m: MoEConfig, act: str):
    """Gather/scatter dispatch — no one-hot contractions (hillclimbed)."""
    G, g, D = xg.shape
    E, k, C = m.num_experts, m.top_k, _capacity(g, m)
    gates, ids, aux = _route(params, xg, m)
    pos, _ = _positions(ids, E, k)
    pos = pos.astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, ids * C + pos, E * C)                    # (G,g,k)
    token_of = jnp.broadcast_to(jnp.arange(g)[None, :, None], (G, g, k))
    xd = jnp.zeros((G, E * C + 1, D), xg.dtype)
    xd = jax.vmap(lambda buf, s, t, x: buf.at[s.reshape(-1)].set(x[t.reshape(-1)]))(
        xd, slot, token_of, xg
    )
    ex_in = (
        xd[:, : E * C, :].reshape(G, E, C, D).transpose(1, 0, 2, 3).reshape(E, G * C, D)
    )
    out = _expert_ffn(params, ex_in, act)
    out = out.reshape(E, G, C, D).transpose(1, 0, 2, 3).reshape(G, E * C, D)
    out = jnp.concatenate([out, jnp.zeros((G, 1, D), out.dtype)], axis=1)
    w = (gates * keep).astype(out.dtype)                            # (G,g,k)
    gathered = jax.vmap(lambda o, s: o[s.reshape(-1)].reshape(g, k, D))(out, slot)
    y = jnp.einsum("Gtkd,Gtk->Gtd", gathered.astype(jnp.float32), w.astype(jnp.float32))
    return y.astype(xg.dtype), aux


def moe_block(
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    dispatch_mode: str = "einsum",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,D) -> (y (B,S,D), aux_loss scalar)."""
    if cfg.moe.held != cfg.moe.num_experts:
        raise ValueError("capacity dispatch needs every expert held")
    B, S, D = x.shape
    G, g = _group(B * S, cfg.moe)
    xg = x.reshape(G, g, D)
    fn = _moe_einsum if dispatch_mode == "einsum" else _moe_gather
    y, aux = fn(params, xg, cfg.moe, cfg.act)
    return y.reshape(B, S, D), aux


def route_weights(params, x, m: MoEConfig) -> jnp.ndarray:
    """Per-token routing weights over all experts, float32 (..., E): the
    chosen experts' gates (``route``), zero elsewhere."""
    _, _, ids, gates = route(params, x, m)
    onehot = jax.nn.one_hot(ids, m.num_experts, dtype=jnp.float32)
    return jnp.einsum("...ke,...k->...e", onehot, gates)


def moe_held(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Drop-free routed experts, x (B,S,D) -> y (B,S,D): the held experts'
    part of the routed sum for every token (see the module docstring)."""
    m: MoEConfig = cfg.moe
    with jax.named_scope("moe.route"):
        w = route_weights(params, x, m)[..., : m.held]
    with jax.named_scope("moe.held"):
        gate = _act(cfg.act)(jnp.einsum("bsd,edf->bsef", x, params["w_gate"]))
        up = jnp.einsum("bsd,edf->bsef", x, params["w_up"])
        out = jnp.einsum("bsef,efd->bsed", gate * up, params["w_down"])
        y = jnp.einsum("bsed,bse->bsd", out.astype(jnp.float32), w)
    return y.astype(x.dtype)
