"""Mesh-sharded draws: shard_map'd tiled kernels + counter RNG.

The paper's technique wins by keeping every access local to one device;
this module keeps that win when the batch spans a mesh.  Row-sharded
weights/tables stay where they live, every shard runs the *same* tiled
kernels the single-device path runs, and all randomness comes from the
counter RNG in :mod:`repro.kernels.rng` seeded by one replicated (2,)
seed pair — so the draw path's jaxpr contains **zero cross-device
collectives** (DESIGN.md §5; ``tests/test_sharded_sampler.py`` gates the
jaxpr).

Layout (1-D data mesh shown; a ('pod', 'data') mesh linearizes):

    weights (B, K)   P('data', None)   rows split, categories whole
    tables / state   P('data', ...)    built per shard by pass A
    phi (factored)   P()               replicated — pass A reads it locally
    seed (2,)        P()               replicated scalar pair
    draws (B,)       P('data')         or (S, B) as P(None, 'data')

Shard s computes its rows' *global* ids from its mesh position
(``axis_index * B_loc + local_row``) and feeds them to the counter RNG,
so draws are bit-identical for 1, 2, or 8 devices — resharding a serving
fleet never changes sampled tokens for a fixed key.

Entry points are consumed through :class:`repro.sampling.SamplerPlan`:
``plan(..., mesh=mesh, spec=...)`` sizes its tiles for the *per-shard*
(B/dev, K) workload and routes ``build``/``draw``/``sample``/
``sample_logits`` here.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import rng as _rng
from repro.sampling import distribution as _dist
from repro.sampling.distribution import Categorical

# mesh axes a batch may shard over, in linearization order (model axes
# never shard the draw: K stays whole so the in-shard walk is local)
DATA_AXES = ("pod", "data")

# state leaves per variant, all row-sharded like the weights that built
# them.  The factored lda_kernel variant is deliberately absent: its
# doc_ids index *local* factor rows, so factored state is always built
# and drawn per shard (repro.lda.distributed), never row-sharded here.
_STATE_LEAVES: Dict[str, Tuple[str, ...]] = {
    "prefix": ("prefix",),
    "fenwick": ("table",),
    "butterfly": ("table",),
    "two_level": ("blocks", "running"),
    "kernel": ("weights", "running"),
    "gumbel": ("logw",),
    "alias": ("alias", "prob"),
    "alias_device": ("alias", "prob"),
    "radix_forest": ("cdf", "root"),
}


def data_axes(mesh: Mesh, spec: Optional[P] = None) -> Tuple[str, ...]:
    """The mesh axes batch rows shard over.

    Default: every 'pod'/'data' axis the mesh has (first axis as a
    fallback for single-axis meshes with another name).  A ``spec``
    overrides: its axis-0 entry names the row axes — e.g. ``P('pod')``
    on a ('pod', 'data') mesh shards rows over pods only."""
    if spec is not None:
        entry = spec[0] if len(spec) else None
        if entry is None:
            raise ValueError(
                f"spec {spec} does not shard axis 0; sharded draws need "
                "row-sharded batches"
            )
        axes = entry if isinstance(entry, tuple) else (entry,)
        missing = [a for a in axes if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"spec {spec} names axes {missing} not on the mesh "
                f"{tuple(mesh.axis_names)}"
            )
        return tuple(axes)
    axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    return axes or (mesh.axis_names[0],)


def data_size(mesh: Mesh, spec: Optional[P] = None) -> int:
    """Number of shards the batch rows split into."""
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh, spec)]))


def row_spec(mesh: Mesh, spec: Optional[P] = None) -> P:
    """PartitionSpec sharding axis 0 over the (spec-overridable) row axes."""
    axes = data_axes(mesh, spec)
    return P(axes if len(axes) > 1 else axes[0])


def mesh_signature(mesh: Optional[Mesh], spec=None) -> Tuple:
    """Hashable topology signature: axis names/sizes, device ids, spec.

    Part of every sharded plan's memo key and tuning bucket — two
    topologies never share a resolved plan (the device-placement
    memoization fix)."""
    if mesh is None:
        return ()
    return (
        tuple(mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
        "" if spec is None else str(spec),
    )


def _linear_index(mesh: Mesh, spec: Optional[P] = None):
    """This shard's linear position along the row axes (traced)."""
    axes = data_axes(mesh, spec)
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _state_specs(method: str, mesh: Mesh, spec: Optional[P] = None) -> Dict[str, P]:
    rs = row_spec(mesh, spec)
    return {k: rs for k in _STATE_LEAVES[method]}


# ---------------------------------------------------------------------------
# The per-shard draw: all variants, all randomness from (row, draw) counters
# ---------------------------------------------------------------------------


def _local_draw(dist: Categorical, seed2, row0, num_samples: int):
    """Draw from a shard-local Categorical with counter RNG.

    ``row0`` is the shard's first *global* row; every random number is a
    pure function of (seed, global row, draw index) — never of the shard
    count or launch layout.  Key-driven variants (gumbel/alias) get their
    own tagged streams so one seed serves every variant.
    """
    B, K = dist.shape
    rows = jnp.asarray(row0, jnp.uint32) + jnp.arange(B, dtype=jnp.uint32)
    if dist.method == "gumbel":
        logw = dist.state["logw"]
        cols = jnp.arange(K, dtype=jnp.uint32)
        tiny = jnp.float32(np.finfo(np.float32).tiny)

        def one(s):
            u = _rng.uniform(
                _rng.fold(seed2, _rng.TAG_GUMBEL, s), rows[:, None],
                cols[None, :],
            )
            g = -jnp.log(-jnp.log(jnp.maximum(u, tiny)))
            return jnp.argmax(logw.astype(jnp.float32) + g, axis=-1).astype(
                jnp.int32
            )

        if num_samples == 1:
            return one(0)
        return jax.vmap(one)(jnp.arange(num_samples, dtype=jnp.uint32))
    if dist.method in ("alias", "alias_device"):
        prob, alias = dist.state["prob"], dist.state["alias"]

        def one(s):
            uj = _rng.uniform(_rng.fold(seed2, _rng.TAG_ALIAS_J, s), rows)
            ua = _rng.uniform(_rng.fold(seed2, _rng.TAG_ALIAS_A, s), rows)
            j = jnp.minimum((uj * K).astype(jnp.int32), K - 1)
            pj = jnp.take_along_axis(prob, j[:, None], axis=1)[:, 0]
            aj = jnp.take_along_axis(alias, j[:, None], axis=1)[:, 0]
            return jnp.where(ua < pj, j, aj).astype(jnp.int32)

        if num_samples == 1:
            return one(0)
        return jax.vmap(one)(jnp.arange(num_samples, dtype=jnp.uint32))
    # u-driven variants: the same rng helpers the kernel-side seed twins
    # use, so the fused-kernel and table-in routes stay bit-identical
    sd = _rng.fold(seed2, _rng.TAG_U, 0)
    if num_samples == 1:
        return _dist._draw_with_u(dist, _rng.row_uniforms(sd, row0, B))
    us = _rng.multi_row_uniforms(sd, row0, B, num_samples)
    if dist.method in ("kernel", "lda_kernel"):
        return _dist._draw_with_u(dist, us)
    return jax.vmap(lambda uu: _dist._draw_with_u(dist, uu))(us)


# ---------------------------------------------------------------------------
# shard_map'd entry points (memoized jitted closures per plan workload)
# ---------------------------------------------------------------------------

_FN_CACHE: Dict[Tuple, object] = {}
_FN_LOCK = threading.Lock()


def _cached_fn(key: Tuple, make):
    with _FN_LOCK:
        fn = _FN_CACHE.get(key)
    if fn is None:
        fn = make()
        with _FN_LOCK:
            fn = _FN_CACHE.setdefault(key, fn)
    return fn


def _out_spec(mesh: Mesh, num_samples: int, spec: Optional[P] = None) -> P:
    rs = row_spec(mesh, spec)
    return rs if num_samples == 1 else P(None, *rs)


def _shard_B(plan) -> int:
    return plan.shape[0] // plan.devices


def _require_key(key) -> None:
    if key is None:
        raise ValueError("sharded draws derive all randomness from a key; "
                         "pass key= (u= is not accepted)")


def _check_shape(plan, arr, what: str):
    arr = jnp.asarray(arr)
    if tuple(arr.shape) != tuple(plan.shape):
        raise ValueError(
            f"plan was made for shape {tuple(plan.shape)}, got {what} of "
            f"shape {tuple(arr.shape)}"
        )
    return arr


def build_sharded(plan, weights) -> Categorical:
    """Pass A per shard: build a row-sharded :class:`Categorical` whose
    state leaves live where their rows live — no resharding, no
    collectives; the jaxpr is ``devices`` independent local builds."""
    mesh = plan.mesh
    B, K = plan.shape
    weights = jnp.asarray(weights)
    if tuple(weights.shape) != (B, K):
        raise ValueError(
            f"plan was made for shape {(B, K)}, got {weights.shape}"
        )
    method, W, tb = plan.table_method, plan.W, plan.tb
    ck = ("build", method, W, tb, plan.shape, mesh_signature(mesh, plan.spec))
    fn = _cached_fn(ck, lambda: jax.jit(
        jax.shard_map(
            lambda w: _dist._build_state(method, w, W),
            mesh=mesh,
            in_specs=(row_spec(mesh, plan.spec),),
            out_specs=_state_specs(method, mesh, plan.spec),
            check_vma=False,  # pallas_call has no replication rule
        )
    ))
    _dist._note_build()
    return Categorical(method=method, W=W, shape=(B, K), state=fn(weights), tb=tb)


def draw_sharded(plan, dist: Categorical, key, num_samples: int = 1):
    """Draw from a sharded distribution: each shard walks its own rows
    with uniforms from (global row, draw) counters.  Returns (B,) global
    indices sharded like the rows ((num_samples, B) for multi-draw)."""
    _require_key(key)
    mesh = plan.mesh
    B, K = dist.shape
    if dist.method in _dist.FACTORED_VARIANTS:
        raise ValueError(
            f"{dist.method!r} state indexes *local* factor rows — row-"
            "sharding a globally built factored distribution would leave "
            "doc_ids pointing past each shard's theta.  Draw factored "
            "state per shard instead (see "
            "repro.lda.distributed.make_sharded_gibbs)"
        )
    if (B, K) != tuple(plan.shape):
        raise ValueError(
            f"plan was made for shape {plan.shape}, got a distribution of "
            f"shape {(B, K)} — global row counters would overlap across "
            "shards; plan the distribution's own shape"
        )
    Bloc = _shard_B(plan)
    method, W, tb = dist.method, dist.W, dist.tb
    ck = (
        "draw", method, W, tb, dist.shape, num_samples,
        mesh_signature(mesh, plan.spec),
    )

    def make():
        def body(state, sd):
            d = Categorical(method=method, W=W, shape=(Bloc, K), state=state,
                            tb=tb)
            return _local_draw(
                d, sd, _linear_index(mesh, plan.spec) * Bloc, num_samples
            )

        sm = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(_state_specs(method, mesh, plan.spec), P()),
            out_specs=_out_spec(mesh, num_samples, plan.spec),
            check_vma=False,  # pallas_call has no replication rule
        )
        # ONE dispatch per draw: key->seed derivation lives inside the jit
        return jax.jit(lambda state, k: sm(state, _rng.seed_from_key(k)))

    return _cached_fn(ck, make)(dist.state, key)


def sample_sharded(plan, weights, key, num_samples: int = 1):
    """One-shot build+draw fused per shard in a single shard_map — the
    sharded analogue of ``SamplerPlan.sample``.  A ``kernel``-variant
    single draw launches the fused Pallas kernel with *in-kernel* counter
    RNG (the (B,) uniform operand does not exist)."""
    _require_key(key)
    mesh = plan.mesh
    B, K = plan.shape
    weights = _check_shape(plan, weights, "weights")
    Bloc = _shard_B(plan)
    method, W, tb, tk = plan.table_method, plan.W, plan.tb, plan.tk
    ck = (
        "sample", method, W, tb, tk, plan.shape, num_samples,
        mesh_signature(mesh, plan.spec),
    )

    def make():
        def body(w, sd):
            row0 = _linear_index(mesh, plan.spec) * Bloc
            if method == "kernel" and num_samples == 1:
                from repro.kernels.butterfly_sample import ops as _kops

                return _kops.butterfly_sample_rng(
                    w, sd, row_offset=row0, W=W, tb=tb or 8, tk=tk or 512
                )
            st = _dist._build_state(method, w, W)
            d = Categorical(method=method, W=W, shape=(Bloc, K), state=st,
                            tb=tb)
            return _local_draw(d, sd, row0, num_samples)

        sm = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(row_spec(mesh, plan.spec), P()),
            out_specs=_out_spec(mesh, num_samples, plan.spec),
            check_vma=False,  # pallas_call has no replication rule
        )
        return jax.jit(lambda x, k: sm(x, _rng.seed_from_key(k)))

    return _cached_fn(ck, make)(weights, key)


def sample_logits_sharded(plan, logits, key, temperature: float = 1.0,
                          num_samples: int = 1, transforms=None):
    """Sharded serving hot path: softmax + build + draw fused per shard
    (one shard_map, no (B, V) weight round-trip through HBM resharding).
    A gumbel plan draws in logit space via counter-Gumbel noise.

    ``transforms`` (a canonical top-k/top-p/min-p chain) routes to
    :func:`sample_logits_truncated_sharded`: parameters broadcast to
    (B,) and row-shard with the logits, thresholds are computed per shard
    (row-local reductions — the zero-collectives gate still holds), and a
    kernel plan launches the fused truncated counter-RNG kernel."""
    if transforms:
        return sample_logits_truncated_sharded(
            plan, logits, key, temperature=temperature,
            num_samples=num_samples, transforms=transforms,
        )
    _require_key(key)
    mesh = plan.mesh
    B, K = plan.shape
    logits = _check_shape(plan, logits, "logits")
    Bloc = _shard_B(plan)
    method, W, tb = plan.table_method, plan.W, plan.tb
    # temperature is a TRACED operand: per-request temperatures share one
    # compiled executable instead of leaking a cache entry per value
    ck = (
        "logits", method, W, tb, plan.tk, plan.shape, num_samples,
        str(logits.dtype), mesh_signature(mesh, plan.spec),
    )

    def make():
        def body(z, temp, sd):
            row0 = _linear_index(mesh, plan.spec) * Bloc
            if method == "gumbel":
                # logit space directly, like the unsharded gumbel path:
                # no exp/log round-trip, so tokens far below the row max
                # keep their (tiny, nonzero) probability
                st = {"logw": (z / temp).astype(jnp.float32)}
            elif method == "kernel" and num_samples == 1:
                # the serving fast path: softmax straight into the fused
                # in-kernel-RNG draw — one launch, no uniform operand
                from repro.kernels.butterfly_sample import ops as _kops

                return _kops.butterfly_sample_rng(
                    _dist.logits_to_weights(z, temp), sd, row_offset=row0,
                    W=W, tb=tb or 8, tk=plan.tk or 512,
                )
            else:
                w = _dist.logits_to_weights(z, temp)
                st = _dist._build_state(method, w, W)
            d = Categorical(method=method, W=W, shape=(Bloc, K), state=st,
                            tb=tb)
            return _local_draw(d, sd, row0, num_samples)

        sm = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(row_spec(mesh, plan.spec), P(), P()),
            out_specs=_out_spec(mesh, num_samples, plan.spec),
            check_vma=False,  # pallas_call has no replication rule
        )
        return jax.jit(
            lambda x, t, k: sm(x, t, _rng.seed_from_key(k))
        )

    return _cached_fn(ck, make)(
        logits, jnp.asarray(temperature, jnp.float32), key
    )


def sample_logits_truncated_sharded(
    plan, logits, key, temperature=1.0, num_samples: int = 1, transforms=(),
):
    """Truncated decode, sharded: temperature + top-k/top-p/min-p per
    shard with all parameters as traced, row-sharded operands.

    The chain must be canonical (at most one TopK -> TopP -> MinP, in
    that order, Temperature anywhere); parameters and temperature
    broadcast to (B,) and shard with the rows, so per-request — even
    per-row — truncation works across any topology.  Thresholds are
    row-local reductions and the RNG is the usual (seed, global row)
    counter, so the draw path keeps ZERO collectives and tokens stay
    bit-identical for 1, 2, or 8 devices at a fixed key."""
    from repro.sampling import transforms as _tr

    _require_key(key)
    mesh = plan.mesh
    B, K = plan.shape
    logits = _check_shape(plan, logits, "logits")
    Bloc = _shard_B(plan)
    kpm = _tr.canonical_params(transforms, B)
    if kpm is None:
        raise ValueError(
            "sharded truncation needs the canonical TopK -> TopP -> MinP "
            "chain (repro.sampling.transforms.chain); reorder or pre-mask "
            "the weights and use plan.sample instead"
        )
    temp = _tr._row(_tr.temperature_of(transforms, temperature), B)
    method, W, tb = plan.table_method, plan.W, plan.tb
    ck = (
        "logits_trunc", method, W, tb, plan.tk, plan.shape, num_samples,
        str(logits.dtype), mesh_signature(mesh, plan.spec),
    )

    def make():
        def body(z, t, prm, sd):
            row0 = _linear_index(mesh, plan.spec) * Bloc
            w = _dist.logits_to_weights(z, t)
            if method == "kernel" and num_samples == 1:
                # fused truncated draw with in-kernel counter RNG: the
                # threshold bisection, masking, block sums and walk all
                # happen on the VMEM-resident tile — per shard, no
                # uniform operand, no collectives
                from repro.kernels.butterfly_sample import ops as _kops

                return _kops.butterfly_sample_truncated_rng(
                    w, sd, prm, row_offset=row0, W=W, tb=tb or 8,
                    tk=plan.tk or 512,
                )
            tau = _tr.thresholds_from_params(w, prm)
            wm = jnp.where(
                w.astype(jnp.float32) >= tau[:, None], w, jnp.zeros_like(w)
            )
            st = _dist._build_state(method, wm, W)
            d = Categorical(method=method, W=W, shape=(Bloc, K), state=st,
                            tb=tb)
            return _local_draw(d, sd, row0, num_samples)

        rs = row_spec(mesh, plan.spec)
        sm = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(rs, rs, rs, P()),
            out_specs=_out_spec(mesh, num_samples, plan.spec),
            check_vma=False,  # pallas_call has no replication rule
        )
        return jax.jit(
            lambda x, t, prm, k: sm(x, t, prm, _rng.seed_from_key(k))
        )

    return _cached_fn(ck, make)(logits, temp, kpm, key)


def place_rows(mesh: Mesh, *arrays):
    """Device_put arrays row-sharded over the mesh's data axes (helper
    for callers staging inputs before a sharded plan call)."""
    sh = NamedSharding(mesh, row_spec(mesh))
    out = tuple(jax.device_put(jnp.asarray(a), sh) for a in arrays)
    return out[0] if len(out) == 1 else out


def reset_sharded_cache() -> None:
    """Drop memoized shard_map closures (test isolation)."""
    with _FN_LOCK:
        _FN_CACHE.clear()
