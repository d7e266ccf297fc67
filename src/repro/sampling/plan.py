"""``SamplerPlan`` — the compiled side of the distribution-object API.

``plan(spec_or_shape, method="auto", ...)`` resolves the method **once
at plan time** — never per draw call — and returns a hashable, frozen
:class:`SamplerPlan` whose ``build``/``draw``/``sample`` methods route
through the jitted kernels in :mod:`repro.sampling.distribution`.
``"auto"`` is turned into a method by one rule, :func:`_resolve_auto`,
from what the call observes (backend, truncation, factored weights,
draws per distribution); it reads no environment variable and no file.
Plans are memoized per (shape, dtype, requested method/W, draws, has_key,
backend, device topology): re-planning the same workload is a dictionary
hit, the resolve counter (:func:`plan_stats`) proves the resolution
count stays at one per distinct workload, and two topologies never share
a plan (a mesh signature joins the key — see ``plan(mesh=...)`` for the
sharded path).

Typical serving wiring (what ``repro.serve.engine`` does)::

    p = sampling.plan((batch, vocab), method=spec.method, W=spec.W)
    # ... inside the jitted decode step:
    next_token = p.sample_logits(logits, key, temperature=0.8)

Typical reuse wiring (the paper's build-once/draw-many pattern)::

    p = sampling.plan(weights.shape, method="fenwick", draws=64)
    dist = p.build(weights)           # tables built exactly once
    for step in range(64):
        idx = p.draw(dist, key=keys[step])
    dist = dist.refreshed(new_weights)  # weights changed -> explicit rebuild
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import runtime
from repro.sampling import distribution as _dist
from repro.sampling.distribution import Categorical, KEY_VARIANTS

# resolved-plan memo + counters.  "auto_resolves" counts how often the
# rule ran; "plan_hits" counts memoized returns.
_PLAN_CACHE: Dict[Tuple, "SamplerPlan"] = {}
_PLAN_LOCK = threading.Lock()
_STATS = {"auto_resolves": 0, "plan_hits": 0, "plan_misses": 0}


def plan_stats() -> dict:
    with _PLAN_LOCK:
        return dict(_STATS)


def reset_plans() -> None:
    """Drop memoized plans and zero the counters (test isolation)."""
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()
        for k in _STATS:
            _STATS[k] = 0
    from repro.sampling import sharded as _sharded

    _sharded.reset_sharded_cache()


@dataclasses.dataclass(frozen=True)
class SamplerPlan:
    """A resolved (method, W) sampling strategy for one (B, K) workload.

    Frozen and hashable — safe to memoize, close over in jitted functions,
    and compare.  ``method`` is always concrete here ("auto" resolved at
    plan time).

    A *sharded* plan (``mesh`` set) was resolved for the per-shard
    (B/devices, K) workload; its ``build``/``draw``/``sample``/
    ``sample_logits`` route through :mod:`repro.sampling.sharded` —
    shard_map'd per-shard kernels with counter RNG, zero collectives on
    the draw path (DESIGN.md §5)."""

    method: str
    W: int
    shape: Tuple[int, int]
    dtype: str
    draws: int
    has_key: bool
    backend: str
    tb: int = 0          # tiled draw-kernel rows per grid step (0 = default)
    tk: int = 0          # pass-A category tile (0 = default)
    factored: bool = False
    mesh: Optional[object] = None    # jax.sharding.Mesh for sharded plans
    spec: Optional[object] = None    # row PartitionSpec override
    devices: int = 1                 # shards the batch rows split into
    transforms: str = ""             # truncation-chain signature ("kpm", ...)

    @property
    def table_method(self) -> str:
        """The buildable Categorical variant behind this plan's method —
        ``kernel_trunc`` is the fused truncated *draw* strategy and
        carries plain ``kernel`` state when a table is built."""
        return "kernel" if self.method == "kernel_trunc" else self.method

    # -- building ----------------------------------------------------------

    def build(self, weights) -> Categorical:
        """Build the plan's :class:`Categorical` from (B, K) weights."""
        if self.method in _dist.FACTORED_VARIANTS:
            raise ValueError(
                f"plan resolved to factored variant {self.method!r}; build "
                "it with build_from_factors(theta, phi, words)"
            )
        if self.mesh is not None:
            from repro.sampling import sharded as _sharded

            return _sharded.build_sharded(self, weights)
        weights = jnp.asarray(weights)
        if tuple(weights.shape) != self.shape:
            raise ValueError(
                f"plan was made for shape {self.shape}, got {weights.shape}"
            )
        return Categorical._build(weights, self.table_method, self.W, self.tb)

    def build_from_logits(
        self, logits, temperature: float = 1.0, transforms=None
    ) -> Categorical:
        """Build the plan's distribution from logits; a ``transforms``
        truncation chain is baked into the table (masked weights — see
        :meth:`Categorical.from_logits`)."""
        if transforms:
            from repro.sampling import transforms as _tr

            return self.build(_tr.apply_to_logits(transforms, logits, temperature))
        return self.build(_dist.logits_to_weights(logits, temperature))

    def build_from_factors(self, theta, phi, words, doc_ids=None) -> Categorical:
        """Build from a (theta, phi, words) factorization — the LDA form.

        A plan resolved to a factored variant (``lda_kernel``) builds its
        block-sum table straight from the factors; any other resolved
        method materializes the per-sample weights first (one fused XLA
        product) and builds normally, so callers can use this entry point
        uniformly whatever method the plan holds.
        """
        if self.mesh is not None:
            raise ValueError(
                "sharded plans don't build factored state globally: doc_ids/"
                "words index *local* factor rows.  Build per shard instead "
                "(plan the per-shard shape with devices=N inside a shard_map "
                "body — see repro.lda.distributed.make_sharded_gibbs)"
            )
        theta = jnp.asarray(theta)
        words = jnp.asarray(words, jnp.int32)
        if doc_ids is None:
            doc_ids = jnp.arange(words.shape[0], dtype=jnp.int32)
        doc_ids = jnp.asarray(doc_ids, jnp.int32)
        if self.method in _dist.FACTORED_VARIANTS:
            return Categorical._build_factored(
                theta, phi, doc_ids, words, self.method, self.W, self.tb
            )
        flat = theta[doc_ids] * jnp.asarray(phi)[words]
        return self.build(flat)

    # -- drawing -----------------------------------------------------------

    def draw(
        self,
        dist: Categorical,
        key: Optional[jax.Array] = None,
        u: Optional[jnp.ndarray] = None,
        num_samples: int = 1,
    ) -> jnp.ndarray:
        """Draw from a built distribution (see :func:`sampling.draw`).

        A sharded plan draws per shard with counter RNG — pass ``key=``
        (``u=`` buffers are exactly what the sharded path deletes)."""
        if self.mesh is not None:
            from repro.sampling import sharded as _sharded

            if u is not None:
                raise ValueError(
                    "sharded plans derive uniforms from the counter RNG; "
                    "pass key= instead of u="
                )
            return _sharded.draw_sharded(self, dist, key, num_samples)
        return _dist.draw(dist, key=key, u=u, num_samples=num_samples)

    def sample(
        self,
        weights,
        key: Optional[jax.Array] = None,
        u: Optional[jnp.ndarray] = None,
        num_samples: int = 1,
    ) -> jnp.ndarray:
        """Build a throwaway distribution and draw — the one-shot path.

        Sharded plans fuse build+draw into one shard_map launch."""
        if self.table_method in _dist.FACTORED_VARIANTS:
            raise ValueError(
                f"plan resolved to factored variant {self.method!r}; build "
                "it with build_from_factors(theta, phi, words) and draw "
                "from that"
            )
        if self.mesh is not None:
            from repro.sampling import sharded as _sharded

            if u is not None:
                raise ValueError(
                    "sharded plans derive uniforms from the counter RNG; "
                    "pass key= instead of u="
                )
            return _sharded.sample_sharded(self, weights, key, num_samples)
        return self.draw(self.build(weights), key=key, u=u, num_samples=num_samples)

    def sample_logits(
        self,
        logits,
        key: jax.Array,
        temperature: float = 1.0,
        num_samples: int = 1,
        transforms=None,
    ) -> jnp.ndarray:
        """Temperature sampling from (B, V) logits (the serving hot path).

        ``temperature == 0`` short-circuits to argmax.  A plan resolved to
        ``gumbel`` samples directly in logit space (no exp/log round-trip),
        matching the legacy ``sample_from_logits`` numerics exactly.

        ``transforms`` is a truncation chain from
        :mod:`repro.sampling.transforms` — its parameters (and
        ``temperature``, scalar or per-row) are traced operands, so one
        compiled decode step serves per-request, even per-row
        heterogeneous, top-k/top-p/min-p.  Execution is butterfly-native:
        a kernel-variant plan runs the fused truncated draw (threshold
        search in-kernel, no sort, no (B, V) sorted copy); other variants
        take the XLA threshold twin and build from masked weights."""
        logits = jnp.asarray(logits)
        if isinstance(temperature, (int, float)) and temperature == 0.0:
            # truncation never removes the modal token, so greedy decode
            # ignores the chain entirely
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if num_samples == 1:
                return greedy
            return jnp.broadcast_to(greedy, (num_samples,) + greedy.shape)
        if self.mesh is not None:
            from repro.sampling import sharded as _sharded

            return _sharded.sample_logits_sharded(
                self, logits, key, temperature=temperature,
                num_samples=num_samples, transforms=transforms,
            )
        if transforms:
            return self._sample_logits_truncated(
                logits, key, temperature, num_samples, transforms
            )
        if self.method == "gumbel":
            from repro.core import gumbel as _gumbel

            if num_samples == 1:
                return _gumbel.draw_gumbel_logits(logits / temperature, key)
            keys = jax.random.split(key, num_samples)
            return jax.vmap(
                lambda k: _gumbel.draw_gumbel_logits(logits / temperature, k)
            )(keys)
        weights = _dist.logits_to_weights(logits, temperature)
        return self.sample(weights, key=key, num_samples=num_samples)

    def _sample_logits_truncated(
        self, logits, key, temperature, num_samples: int, transforms
    ) -> jnp.ndarray:
        from repro.sampling import transforms as _tr

        temp = _tr.temperature_of(transforms, temperature)
        trunc = _tr.truncations_of(transforms)
        if not trunc:
            return self.sample_logits(
                logits, key, temperature=temp, num_samples=num_samples
            )
        B = logits.shape[0]
        kpm = _tr.canonical_params(transforms, B)
        if (
            self.method in ("kernel", "kernel_trunc")
            and num_samples == 1
            and kpm is not None
        ):
            # the decode fast path: softmax straight into the ONE-kernel
            # fused truncated draw (threshold bisection on the
            # VMEM-resident tile; masked two-pass route at vocab scale)
            from repro.kernels.butterfly_sample import ops as _kops

            w = _dist.logits_to_weights(logits, temp)
            u = jax.random.uniform(key, (B,), dtype=jnp.float32)
            return _kops.butterfly_sample_truncated(
                w, u, kpm, W=self.W, tb=self.tb or 8, tk=self.tk or 512
            )
        w = _dist.logits_to_weights(logits, temp)
        if self.method == "gumbel":
            # stay in logit space: mask the truncated tokens to -inf and
            # gumbel-argmax the survivors (their relative logits are
            # untouched, so this IS the renormalized truncated draw)
            from repro.core import gumbel as _gumbel

            tau = _tr.thresholds(w, trunc)
            t = jnp.asarray(temp)
            z = logits / (t[:, None] if t.ndim == 1 else t)
            zm = jnp.where(
                w.astype(jnp.float32) >= tau[:, None], z,
                jnp.asarray(-jnp.inf, z.dtype),
            )
            if num_samples == 1:
                return _gumbel.draw_gumbel_logits(zm, key)
            keys = jax.random.split(key, num_samples)
            return jax.vmap(lambda k: _gumbel.draw_gumbel_logits(zm, k))(keys)
        wm = _tr.apply(w, trunc)
        return self.sample(wm, key=key, num_samples=num_samples)


def _normalize_shape(spec_or_shape, shape) -> Tuple[int, int]:
    if hasattr(spec_or_shape, "shape") and not isinstance(spec_or_shape, tuple):
        spec_or_shape = spec_or_shape.shape
    if isinstance(spec_or_shape, (tuple, list)) and len(spec_or_shape) == 2:
        return (int(spec_or_shape[0]), int(spec_or_shape[1]))
    if shape is not None and len(shape) == 2:
        return (int(shape[0]), int(shape[1]))
    raise ValueError(
        "plan() needs a (B, K) workload shape: pass a 2-tuple, an array, "
        "or a SamplerSpec together with shape=(B, K)"
    )


def _resolve_auto(
    backend: str, *, factored: bool, transforms: str, draws: int
) -> str:
    """The one rule behind ``method="auto"``.

    A truncated draw takes the fused truncated kernel, a factored draw
    the fused factored kernel, a table drawn from many times Fenwick's
    cached table, and any other draw the fused kernel.  The Pallas
    kernels run only where they compile natively; elsewhere the flat
    draws take their pure-XLA twin, ``two_level``, and ``lda_kernel``
    its own.  Every answer is an exact draw from the same distribution.
    """
    native = not runtime.default_interpret(backend)
    if transforms:
        return "kernel_trunc" if native else "two_level"
    if factored:
        return "lda_kernel"
    if draws > 1:
        return "fenwick"
    return "kernel" if native else "two_level"


def plan(
    spec_or_shape,
    method: Optional[str] = None,
    *,
    shape: Optional[Tuple[int, int]] = None,
    W: Optional[int] = None,
    dtype: Union[str, jnp.dtype] = "float32",
    draws: int = 1,
    has_key: bool = True,
    backend: Optional[str] = None,
    factored: bool = False,
    mesh=None,
    spec=None,
    devices: Optional[int] = None,
    transforms="",
) -> SamplerPlan:
    """Resolve a sampling strategy for a workload, once.

    ``spec_or_shape`` is a (B, K) tuple, an array (shape and dtype are
    taken from it), or a ``repro.configs.base.SamplerSpec`` (method/W/draws
    are taken from it; pass the workload via ``shape=``).

    ``method="auto"`` (the default) is resolved by :func:`_resolve_auto`
    exactly once per distinct (shape, dtype, draws, has_key, backend,
    topology): results are memoized process-wide, and draw calls made
    through the returned plan never re-resolve.  ``W`` falsy means
    ``runtime.default_w(K)``; the tiles are ``runtime.default_tb`` of the
    per-shard rows and ``runtime.default_tk(K, W)``.

    ``mesh=`` makes the plan *sharded*: (B, K) is the global workload,
    rows shard over the mesh's data axes (``spec=`` overrides the row
    PartitionSpec), the tiles are sized for the **per-shard** (B/dev, K)
    shape, and the topology signature joins the memo key — a plan made
    for one topology is never silently reused for another.
    ``devices=`` (without a mesh) marks callers that are *already*
    per-shard, e.g. inside a shard_map body (the shape is then NOT
    divided further).

    ``transforms=`` declares a truncation workload: a chain (or its
    :func:`repro.sampling.transforms.signature` string, e.g. ``"kpm"``)
    joins the memo key and lets ``auto`` pick the fused ``kernel_trunc``
    draw, but parameter *values* stay out of the key, so per-request p/k
    share one plan and one executable.
    """
    # unpack a SamplerSpec-shaped object (duck-typed: configs may not be
    # importable in every context this runs)
    if hasattr(spec_or_shape, "method") and hasattr(spec_or_shape, "W"):
        sspec = spec_or_shape
        method = method if method not in (None, "auto") else sspec.method
        W = W or (sspec.W or None)
        draws = max(draws, getattr(sspec, "draws", 1))
        spec_or_shape = None
    if hasattr(spec_or_shape, "dtype") and hasattr(spec_or_shape, "shape"):
        dtype = str(spec_or_shape.dtype)
    method = method or "auto"
    B, K = _normalize_shape(spec_or_shape, shape)
    dtype_name = str(jnp.dtype(dtype)) if not isinstance(dtype, str) else dtype
    if transforms and not isinstance(transforms, str):
        from repro.sampling import transforms as _tr

        transforms = _tr.signature(transforms)
    transforms = transforms or ""

    if backend is None:
        backend = jax.default_backend()
    mesh_sig: Tuple = ()
    if mesh is not None:
        from repro.sampling import sharded as _sharded

        nd = _sharded.data_size(mesh, spec)   # validates spec axes too
        if B % nd:
            raise ValueError(
                f"cannot shard B={B} rows over {nd} devices along "
                f"{_sharded.data_axes(mesh, spec)}: not divisible"
            )
        if devices not in (None, nd):
            raise ValueError(
                f"devices={devices} contradicts the mesh's {nd} data shards"
            )
        devices = nd
        B_res = B // nd          # the tiles are sized per shard
        mesh_sig = _sharded.mesh_signature(mesh, spec)
    else:
        if spec is not None:
            raise ValueError(
                "spec= only has meaning with mesh=: an unsharded plan "
                "would silently ignore it"
            )
        devices = int(devices or 1)
        B_res = B                # caller is already per-shard (or unsharded)
    key = (
        B, K, dtype_name, method, W or 0, int(draws), bool(has_key), backend,
        bool(factored), int(devices), mesh_sig, transforms,
    )
    with _PLAN_LOCK:
        hit = _PLAN_CACHE.get(key)
        if hit is not None:
            _STATS["plan_hits"] += 1
            return hit
        _STATS["plan_misses"] += 1

    resolved = method
    if method == "auto":
        with _PLAN_LOCK:
            _STATS["auto_resolves"] += 1
        resolved = _resolve_auto(
            backend, factored=factored, transforms=transforms, draws=draws
        )
    W = int(W or runtime.default_w(K))

    p = SamplerPlan(
        method=resolved,
        W=W,
        shape=(B, K),
        dtype=dtype_name,
        draws=int(draws),
        has_key=bool(has_key),
        backend=backend,
        tb=runtime.default_tb(B_res),
        tk=runtime.default_tk(K, W),
        factored=bool(factored),
        mesh=mesh,
        spec=spec,
        devices=int(devices),
        transforms=transforms,
    )
    with _PLAN_LOCK:
        _PLAN_CACHE.setdefault(key, p)
    return p
