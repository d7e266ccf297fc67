"""Serving engine: batched prefill + decode with the butterfly sampler.

Token sampling from a vocab-sized categorical per sequence is *exactly* the
paper's setting (K = vocab, one distribution per batch row, each table used
once) — the decode step's sampler is the paper's technique as a first-class
serving feature.  Since the distribution-object redesign the engine builds
a :class:`repro.sampling.SamplerPlan` in ``make_decode_step`` /
``make_serve_step`` / ``make_prefill_step`` — ``ModelConfig.sampler_spec``
(a ``SamplerSpec``) is resolved by ``sampling.plan``'s rule **once per
(B, vocab) workload at plan time**, not re-dispatched from strings on
every step; the jitted step then draws through the plan's compiled path.

Multi-draw decode (``make_decode_step(..., num_samples=n)``) samples n
candidate tokens per sequence from one built distribution per step; for a
kernel-variant plan all B*n walks run in ONE tiled pass-B launch.

Sharded decode (``make_decode_step(..., mesh=mesh)``) row-shards the
sequences over the mesh's data axes and samples per shard through the
shard_map'd kernel path with counter RNG — no collectives on the draw
path, no per-draw key splitting, and tokens independent of the device
count for a fixed key (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sampling
from repro.configs.base import ModelConfig
from repro.models.model import Model
from repro.models.params import init_params


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, max_new)
    steps: int
    prefill_len: int


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode sampling controls — a registered pytree whose
    leaves (``temperature``/``top_k``/``top_p``/``min_p``) are *traced*
    operands of the decode step: one compiled executable serves any mix
    of values, including per-row (B,) arrays for heterogeneous batches
    (request i gets its own top-p).

    ``temperature=None`` (the default) defers to the engine's
    ``temperature`` argument; a numeric/array value overrides it and must
    be > 0 (greedy decode is the engine's ``temperature=0``, decided at
    trace time).  ``top_k=0`` / ``top_p=1.0`` / ``min_p=0.0`` disable the
    respective truncation — per row, when arrays."""

    temperature: object = None
    top_k: object = 0
    top_p: object = 1.0
    min_p: object = 0.0

    def transforms(self):
        """The truncation chain (canonical top-k -> top-p -> min-p; the
        temperature is threaded separately so greedy stays decidable)."""
        from repro.sampling import transforms as _tr

        return _tr.chain(top_k=self.top_k, top_p=self.top_p, min_p=self.min_p)


jax.tree_util.register_pytree_node(
    SamplingParams,
    lambda sp: ((sp.temperature, sp.top_k, sp.top_p, sp.min_p), None),
    lambda aux, ch: SamplingParams(*ch),
)

def _sp_sig(sp: Optional["SamplingParams"]) -> str:
    """The transforms signature a SamplingParams default actually runs
    (statically-disabled stages are dropped by ``transforms.chain``), for
    the plan memo key."""
    if sp is None:
        return ""
    from repro.sampling import transforms as _tr

    return _tr.signature(sp.transforms())


def default_sampling_params(cfg: ModelConfig) -> Optional[SamplingParams]:
    """The config's model-card decode defaults lifted into
    ``SamplingParams`` — ``None`` when the spec doesn't truncate (plain
    temperature decode keeps the untruncated fast path)."""
    spec = cfg.sampler_spec
    if not spec.truncates:
        return None
    return SamplingParams(
        top_k=spec.top_k, top_p=spec.top_p, min_p=spec.min_p
    )


def _logits_plan(cfg: ModelConfig, B: int, V: int, dtype_name: str,
                 draws: int = 1, mesh=None, transforms: str = ""):
    """The config's sampler spec, planned for a (B, V) logits workload.

    ``sampling.plan`` memoizes process-wide, so this resolves the method on
    the first (shape, dtype) sighting and is a dictionary hit after —
    whether called eagerly (known batch size) or at trace time.
    ``draws`` is the per-distribution reuse hint (multi-draw decode).
    ``mesh`` makes the plan sharded: sequences row-shard over the mesh's
    data axes and the sampler runs per shard (the topology is part of the
    plan memo key, so one engine can serve several meshes).
    ``transforms`` is the truncation-chain signature for truncated decode
    (joins the plan memo key; parameter values stay out)."""
    spec = cfg.sampler_spec
    return sampling.plan(
        (B, V), method=spec.method, W=spec.W or None, dtype=dtype_name,
        draws=max(spec.draws, draws), has_key=True, mesh=mesh,
        transforms=transforms,
    )


# sentinel distinguishing "``sampling`` not given -> factory defaults"
# from an explicit ``sampling=None`` -> plain untruncated decode
_SP_UNSET = object()


def _chain_for(sp: "SamplingParams", sig: str):
    """The truncation chain matching a *static* signature, carrying THIS
    call's (possibly traced) parameter leaves.  Rebuilding the chain from
    traced leaves via ``sp.transforms()`` would resurrect statically
    dropped stages (a tracer is never "statically disabled"); selecting
    stages by the signature keeps the executable and the plan's memo key
    mutually consistent."""
    from repro.sampling import transforms as _tr

    out = []
    if "k" in sig:
        out.append(_tr.TopK(sp.top_k))
    if "p" in sig:
        out.append(_tr.TopP(sp.top_p))
    if "m" in sig:
        out.append(_tr.MinP(sp.min_p))
    return tuple(out)


def make_decode_step(
    model: Model,
    temperature: float = 1.0,
    batch_size: Optional[int] = None,
    num_samples: int = 1,
    mesh=None,
    sampling_params: Optional[SamplingParams] = None,
):
    """Jitted decode step: (params, caches, token, pos, key[, sampling])
    -> (next_token(s), logits, caches).

    When ``batch_size`` is known up front the sampler plan is built (and
    its method resolved) eagerly, before the first trace; otherwise planning
    happens at trace time on first use and is memoized after.

    ``num_samples > 1`` draws that many candidate tokens per sequence from
    ONE built distribution (speculative/best-of-n decode): the step
    returns (B, num_samples) candidates, the plan is resolved with the
    reuse hint ``draws=num_samples``, and a kernel-variant plan walks all
    B*num_samples draws in a single tiled pass-B launch (the ``rows``
    indirection in the kernel) instead of rebuilding tables per draw.

    ``mesh`` makes the decode step *sharded*: sequences (and their
    logits) row-shard over the mesh's data axes, and the sampler runs as
    a shard_map of the same tiled kernels with counter RNG — zero
    collectives on the draw path, tokens bit-identical for any device
    count at a fixed key (DESIGN.md §5).  Requires ``batch_size`` (or the
    first traced batch) divisible by the data-shard count.

    The returned step ALWAYS accepts an optional trailing ``sampling``
    argument, whatever the factory arguments were: omitted, it falls back
    to ``sampling_params`` (else the model config's
    ``SamplerSpec.top_k/top_p/min_p`` model-card defaults, else plain
    untruncated decode); an explicit ``sampling=None`` forces the plain
    untruncated path for that call; a :class:`SamplingParams` runs
    truncated decode with *that call's* parameters — its leaves are
    traced, so per-request (even per-row ``(B,)`` heterogeneous) values
    reuse one compiled executable.  The truncation chain's *shape* (which
    stages exist) is resolved per call from the concrete parameters and
    threaded statically, so a call can never silently inherit an earlier
    call's (or the factory default's) stage set — only calls that change
    which stages are statically enabled retrace.  Execution is
    butterfly-native (fused threshold pass, no vocab sort — see
    ``repro.sampling.transforms``)."""
    cfg = model.cfg
    sp0 = sampling_params if sampling_params is not None else (
        default_sampling_params(cfg)
    )
    if batch_size is not None:
        _logits_plan(cfg, batch_size, cfg.padded_vocab, "float32",
                     draws=num_samples, mesh=mesh, transforms=_sp_sig(sp0))

    def _shape(nxt, logits, caches):
        if num_samples == 1:
            return nxt[:, None].astype(jnp.int32), logits, caches
        return nxt.T.astype(jnp.int32), logits, caches  # (B, num_samples)

    @jax.jit
    def plain_step(params, caches, token, pos, key):
        logits, caches = model.decode(params, caches, token, pos)
        p = _logits_plan(
            cfg, logits.shape[0], logits.shape[1], str(logits.dtype),
            draws=num_samples, mesh=mesh,
        )
        nxt = p.sample_logits(
            logits, key, temperature=temperature, num_samples=num_samples
        )
        return _shape(nxt, logits, caches)

    @functools.partial(jax.jit, static_argnames=("sig",))
    def trunc_step(params, caches, token, pos, key, sampling, sig):
        logits, caches = model.decode(params, caches, token, pos)
        p = _logits_plan(
            cfg, logits.shape[0], logits.shape[1], str(logits.dtype),
            draws=num_samples, mesh=mesh, transforms=sig,
        )
        temp = (
            sampling.temperature if sampling.temperature is not None
            else temperature
        )
        tr = _chain_for(sampling, sig)
        nxt = p.sample_logits(
            logits, key, temperature=temp, num_samples=num_samples,
            transforms=tr if tr else None,
        )
        return _shape(nxt, logits, caches)

    def step(params, caches, token, pos, key, sampling=_SP_UNSET):
        sp = sp0 if sampling is _SP_UNSET else sampling
        if sp is None:
            return plain_step(params, caches, token, pos, key)
        return trunc_step(params, caches, token, pos, key, sp, sig=_sp_sig(sp))

    # the zero-retrace gate reads these (tests, serve bench)
    step.plain_cache_size = plain_step._cache_size
    step.trunc_cache_size = trunc_step._cache_size
    return step


# cache leaves with a (L, B, S, ...) sequence axis (axis 2)
_SEQ_CACHE_LEAVES = frozenset({"k", "v", "c_kv", "k_pe", "self_k", "self_v"})


def _pad_caches_to(caches, target_len: int):
    """Grow attention caches (L, B, S, ...) along the seq axis to target.

    Caches already at (or beyond) ``target_len`` are returned *as-is* —
    the identical pytree, no per-leaf dispatch — so callers can re-pad
    unconditionally (repeated ``generate`` over one cache, the serve
    engine's admission path) without paying a device round-trip for a
    no-op."""
    def _names(path):
        return {getattr(k, "key", None) for k in path}

    if all(
        leaf.shape[2] >= target_len
        for path, leaf in jax.tree_util.tree_leaves_with_path(caches)
        if _names(path) & _SEQ_CACHE_LEAVES
    ):
        return caches

    def pad(path, leaf):
        if _names(path) & _SEQ_CACHE_LEAVES:
            cur = leaf.shape[2]
            if cur < target_len:
                pads = [(0, 0), (0, 0), (0, target_len - cur)] + [(0, 0)] * (leaf.ndim - 3)
                return jnp.pad(leaf, pads)
        return leaf

    return jax.tree_util.tree_map_with_path(pad, caches)


def generate(
    model: Model,
    params,
    batch: Dict,
    max_new_tokens: int = 16,
    temperature: float = 1.0,
    key: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
    mesh=None,
) -> GenerationResult:
    """Prefill the prompt batch, then decode ``max_new_tokens`` greedily or
    by sampling.  Python loop around a jitted step (engine-style).

    ``mesh`` shards the decode sampler like :func:`make_decode_step`:
    sequences row-shard over the mesh's data axes and the draw runs
    through the shard_map'd counter-RNG path (the launch/serve (dp, tp)
    wiring).  The prompt batch must divide by the data-shard count."""
    cfg = model.cfg
    key = key if key is not None else jax.random.PRNGKey(0)
    last_logits, caches = model.prefill(params, batch)
    toks = batch["tgt_tokens"] if "tgt_tokens" in batch else batch["tokens"]
    B, S = toks.shape
    prefix = cfg.meta_tokens + (
        batch["frontend_embeds"].shape[1] if "frontend_embeds" in batch else 0
    )
    prefill_len = S + prefix
    caches = _pad_caches_to(caches, prefill_len + max_new_tokens)

    step_fn = make_decode_step(model, temperature, batch_size=B, mesh=mesh)
    k0, key = jax.random.split(key)
    sp0 = default_sampling_params(cfg)  # model-card truncation, if any
    first_plan = _logits_plan(
        cfg, last_logits.shape[0], last_logits.shape[1],
        str(last_logits.dtype), mesh=mesh, transforms=_sp_sig(sp0),
    )
    first = first_plan.sample_logits(
        last_logits, k0, temperature=temperature,
        transforms=sp0.transforms() if sp0 else None,
    )[:, None].astype(jnp.int32)

    out = [np.asarray(first)]
    token = first
    done = np.zeros((B,), bool)
    for t in range(max_new_tokens - 1):
        key, sub = jax.random.split(key)
        token, _, caches = step_fn(
            params, caches, token, jnp.int32(prefill_len + t), sub
        )
        arr = np.asarray(token)
        if eos_id is not None:
            done |= (arr[:, 0] == eos_id)
            if done.all():
                out.append(arr)
                break
        out.append(arr)
    tokens = np.concatenate(out, axis=1)
    return GenerationResult(tokens=tokens, steps=tokens.shape[1], prefill_len=prefill_len)


def make_serve_step(
    model: Model, temperature: float = 1.0, batch_size: Optional[int] = None,
    mesh=None, sampling_params: Optional[SamplingParams] = None,
):
    """The dry-run target: one fused decode+sample step as a pure function
    (params, caches, token, pos, key) -> (next_token, caches).
    ``mesh`` shards the sampler like :func:`make_decode_step`;
    ``sampling_params`` (explicit only — the dry-run contract keeps the
    5-argument signature, so config defaults are not auto-applied here)
    bakes a truncation chain into the step."""
    cfg = model.cfg
    sig = _sp_sig(sampling_params)
    if batch_size is not None:
        _logits_plan(cfg, batch_size, cfg.padded_vocab, "float32", mesh=mesh,
                     transforms=sig)

    def serve_step(params, caches, token, pos, key):
        logits, caches = model.decode(params, caches, token, pos)
        p = _logits_plan(cfg, logits.shape[0], logits.shape[1],
                         str(logits.dtype), mesh=mesh, transforms=sig)
        if sampling_params is None:
            nxt = p.sample_logits(logits, key, temperature=temperature)
        else:
            temp = (
                sampling_params.temperature
                if sampling_params.temperature is not None else temperature
            )
            nxt = p.sample_logits(
                logits, key, temperature=temp,
                transforms=sampling_params.transforms(),
            )
        return nxt.astype(jnp.int32), caches

    return serve_step


def make_prefill_step(
    model: Model, temperature: float = 1.0, batch_size: Optional[int] = None
):
    """Dry-run prefill target: (params, batch, key) -> (first_token, caches)."""
    cfg = model.cfg
    if batch_size is not None:
        _logits_plan(cfg, batch_size, cfg.padded_vocab, "float32")

    def prefill_step(params, batch, key):
        last_logits, caches = model.prefill(params, batch)
        p = _logits_plan(
            cfg, last_logits.shape[0], last_logits.shape[1], str(last_logits.dtype)
        )
        nxt = p.sample_logits(last_logits, key, temperature=temperature)
        return nxt.astype(jnp.int32), caches

    return prefill_step
