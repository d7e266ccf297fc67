"""Measured tuner (autotune layer 2).

``Tuner.resolve`` is the single entry point behind ``method="auto"``: it
maps a workload descriptor (B, K, draws, dtype, has key?) to a concrete
(method, W) pair.

Resolution order:

  1. in-memory / persisted :class:`TuningCache` hit for the shape bucket
     (a measured or bench-imported winner beats a cost-model guess),
  2. on miss, mode ``measure``: time every candidate on synthetic data of
     the *real* shape, persist the winner (``source="measured"``),
  3. on miss, mode ``model`` (the default): rank candidates with the
     analytical cost model, persist the pick (``source="model"``) so the
     next process skips even the model walk,
  4. mode ``off``: cost model every time, nothing persisted.

The mode comes from ``$REPRO_AUTOTUNE`` (``measure`` | ``model`` | ``off``).
``measure`` re-tunes buckets whose cached entry is only a model guess and
upgrades them in place.

``resolve`` is safe to call during ``jax.jit`` tracing (the serve engine's
decode step resolves there): it only consults static shapes.  Timing,
however, is NOT trace-safe — on current jax a nested jitted call made
during an outer trace is staged rather than executed, so a stopwatch
around it measures tracing time.  ``resolve`` therefore never measures
while a trace is active: it falls back to the cost model and persists the
pick as ``source="model"`` so a later eager measure-mode resolve upgrades
it with a real timing.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from repro.autotune import cost_model
from repro.autotune.cache import TuningCache, bucket_key

# methods that draw from a precomputed uniform ``u`` — always candidates
U_METHODS = ("prefix", "fenwick", "two_level", "butterfly", "radix_forest")
# methods that need a PRNG key — candidates only when the caller has one
KEY_METHODS = ("gumbel", "alias", "alias_device")
# every strategy any resolver can ever return — the ingest whitelist
# (bench files also carry non-runnable comparison pseudo-rows)
KNOWN_METHODS = U_METHODS + KEY_METHODS + (
    "kernel", "kernel_trunc", "lda_kernel", "sparse_mh",
)

MODES = ("measure", "model", "off")


@dataclasses.dataclass(frozen=True)
class Resolution:
    """A full tuner answer: strategy plus the tiled-kernel parameters.

    ``tb`` (draw-kernel rows per grid step) and ``tk`` (pass-A category
    tile) matter only to the kernel-backed methods but are recorded for
    every bucket so a cache hit restores the complete launch config."""

    method: str
    W: int
    tb: int
    tk: int
    source: str = "model"

    def pair(self) -> Tuple[str, int]:
        return self.method, self.W


def _mode_from_env() -> str:
    mode = os.environ.get("REPRO_AUTOTUNE", "model").lower()
    return mode if mode in MODES else "model"


def _tracing_active() -> bool:
    """True while inside a jax trace, where wall-clock timing would
    measure tracing (staged nested jits), not execution."""
    import jax

    return not jax.core.trace_ctx.is_top_level()


def candidate_methods(
    B: int, K: int, backend: str, has_key: bool, factored: bool = False,
    transforms: str = "", sparse: bool = False,
) -> Tuple[str, ...]:
    """All viable strategies for this workload: core u-based methods,
    key-based methods when a key is available, plus whatever the kernels
    registry says runs well on this backend.  ``factored=True`` (the
    weights arrive as a theta-phi product — the LDA z-draw) additionally
    admits the fused factored kernels; a non-empty ``transforms``
    signature (a truncated-decode workload) admits the fused truncated
    variants (``kernel_trunc``); ``sparse=True`` (the LDA sweep can hold
    sparse doc-topic counts) admits the MH sweep (``sparse_mh``)."""
    from repro import kernels

    cands = list(U_METHODS)
    if has_key:
        cands.extend(KEY_METHODS)
    cands.extend(
        kernels.candidates(
            B, K, backend, factored=factored, truncated=bool(transforms),
            sparse=sparse,
        )
    )
    # the kernels registry doesn't know about PRNG keys: drop any
    # registry-contributed key-driven strategy (alias_device) for u-based
    # callers — they could never run its draw
    if not has_key:
        cands = [c for c in cands if c not in KEY_METHODS]
    return tuple(dict.fromkeys(cands))  # dedupe, keep order


def measure_method(
    method: str,
    B: int,
    K: int,
    W: int,
    *,
    dtype=None,
    iters: int = 3,
    warmup: int = 1,
    seed: int = 0,
    factored: bool = False,
    truncated: bool = False,
    sparse: bool = False,
) -> Optional[float]:
    """Median wall-clock microseconds of one jitted (B, K) draw batch on
    synthetic weights; ``None`` if the method does not serve this kind of
    workload.  A compile or run error propagates: a candidate the
    compiler refuses is a fault to fix, not a slow candidate.

    ``factored=True`` times the workload the factored buckets describe:
    weights arrive as a theta-phi product, so flat-weight methods are
    timed *including* the gather + (B, K) materialization they really
    pay there — otherwise measure mode would systematically undercount
    them against ``lda_kernel``.

    ``truncated=True`` times the truncated-decode workload at a
    representative (top_k, top_p) = (max(K//8, 1), 0.9): ``kernel_trunc``
    runs its fused threshold+draw; every other method is timed
    *including* the XLA threshold search + masking it really pays
    there."""
    import jax
    import jax.numpy as jnp

    from repro.core import api as _api

    dtype = dtype or jnp.float32
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, K)), dtype=dtype)
    u = jnp.asarray(rng.uniform(0.0, 1.0, size=(B,)), jnp.float32)
    key = jax.random.PRNGKey(seed)
    if truncated:
        from repro.sampling import transforms as _tr

        trunc_chain = _tr.chain(top_k=max(K // 8, 1), top_p=0.9)
        kpm = _tr.canonical_params(trunc_chain, B)
    if factored:
        # an LDA-shaped factorization at the real (B, K)
        C, V = max(1, B // 32), 64
        theta = jnp.asarray(rng.uniform(0.1, 1.0, size=(C, K)), dtype=dtype)
        phi = jnp.asarray(rng.uniform(0.1, 1.0, size=(V, K)), dtype=dtype)
        doc_ids = jnp.asarray(rng.integers(0, C, size=(B,)), jnp.int32)
        words = jnp.asarray(rng.integers(0, V, size=(B,)), jnp.int32)

    if method == "sparse_mh":
        if not sparse:
            return None
        from repro.lda import sparse as _sparse

        return _sparse.measure_sparse_mh(
            B, K, iters=iters, warmup=warmup, seed=seed
        )
    if method == "kernel_trunc":
        if not truncated:
            return None
        from repro.kernels.butterfly_sample import ops as _kops

        fn = jax.jit(
            lambda w, uu: _kops.butterfly_sample_truncated(
                w, uu, kpm, W=W
            )
        )
        args = (w, u)
    elif truncated and method not in KEY_METHODS and not factored:
        from repro.sampling import transforms as _tr

        fn = jax.jit(
            lambda w, uu: _api.sample_categorical(
                _tr.apply(w, trunc_chain), u=uu, method=method, W=W
            )
        )
        args = (w, u)
    elif truncated and method in KEY_METHODS and not factored:
        from repro.sampling import transforms as _tr

        fn = jax.jit(
            lambda w, k: _api.sample_categorical(
                _tr.apply(w, trunc_chain), key=k, method=method, W=W
            )
        )
        args = (w, key)
    elif method in cost_model.FACTORED_METHODS:
        if not factored:
            return None
        from repro.kernels.lda_draw import lda_draw_factored

        fn = jax.jit(
            lambda th, ph, uu: lda_draw_factored(
                th, ph, doc_ids, words, uu, W=W
            )
        )
        args = (theta, phi, u)
    elif factored and method not in KEY_METHODS:
        fn = jax.jit(
            lambda th, ph, uu: _api.sample_categorical(
                th[doc_ids] * ph[words], u=uu, method=method, W=W
            )
        )
        args = (theta, phi, u)
    elif factored and method in KEY_METHODS:
        fn = jax.jit(
            lambda th, ph, k: _api.sample_categorical(
                th[doc_ids] * ph[words], key=k, method=method, W=W
            )
        )
        args = (theta, phi, key)
    elif method in KEY_METHODS:
        fn = jax.jit(
            lambda w, k: _api.sample_categorical(w, key=k, method=method, W=W)
        )
        args = (w, key)
    else:
        fn = jax.jit(
            lambda w, u: _api.sample_categorical(w, u=u, method=method, W=W)
        )
        args = (w, u)
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


class Tuner:
    """Workload -> (method, W) resolver with a persistent winner cache."""

    def __init__(
        self,
        cache: Optional[TuningCache] = None,
        mode: Optional[str] = None,
        backend: Optional[str] = None,
    ):
        self.cache = cache if cache is not None else TuningCache()
        self._mode = mode
        self._backend = backend

    @property
    def mode(self) -> str:
        return self._mode or _mode_from_env()

    @property
    def backend(self) -> str:
        if self._backend is None:
            import jax

            self._backend = jax.default_backend()
        return self._backend

    # -- the entry point behind method="auto" -----------------------------

    def resolve(
        self,
        B: int,
        K: int,
        *,
        draws: int = 1,
        dtype_name: str = "float32",
        has_key: bool = True,
        factored: bool = False,
        devices: int = 1,
        transforms: str = "",
        sparse: bool = False,
        kd: Optional[float] = None,
        candidates: Optional[Sequence[str]] = None,
    ) -> Tuple[str, int]:
        """Back-compat (method, W) resolution; see :meth:`resolve_full`."""
        return self.resolve_full(
            B, K, draws=draws, dtype_name=dtype_name, has_key=has_key,
            factored=factored, devices=devices, transforms=transforms,
            sparse=sparse, kd=kd, candidates=candidates,
        ).pair()

    def resolve_full(
        self,
        B: int,
        K: int,
        *,
        draws: int = 1,
        dtype_name: str = "float32",
        has_key: bool = True,
        factored: bool = False,
        devices: int = 1,
        transforms: str = "",
        sparse: bool = False,
        kd: Optional[float] = None,
        candidates: Optional[Sequence[str]] = None,
    ) -> Resolution:
        """Full resolution including the tiled-kernel ``tb``/``tk``
        launch parameters (v2+ cache records persist them; v1 records fall
        back to the kernel defaults for the bucket shape).

        ``devices > 1`` marks a mesh-sharded workload: ``B`` is the
        *per-shard* row count (the shape the shard's kernels actually
        launch with — that is what candidates are measured/modeled at)
        and the winner lands in the topology's own v3 cache bucket.

        A non-empty ``transforms`` signature (``"k"``/``"kp"``/``"kpm"``
        ... — see ``repro.sampling.transforms.signature``) marks a
        truncated-decode workload: the fused truncated kernel joins the
        candidate set, every candidate is costed *including* its
        threshold-search surcharge, and the winner lands in the
        signature's own v4 cache bucket.

        ``sparse=True`` marks an LDA z-draw whose sweep can hold sparse
        doc-topic counts: the MH sweep (``sparse_mh``) joins the
        candidate set — the only method sublinear in K — and the winner
        lands in the workload's own v5 ``|sp`` bucket.  ``kd`` (optional,
        model mode only) is the observed mean live topics per doc."""
        backend = self.backend
        cands = tuple(
            candidates
            if candidates is not None
            else candidate_methods(
                B, K, backend, has_key, factored=factored,
                transforms=transforms, sparse=sparse,
            )
        )
        mode = self.mode
        truncated = bool(transforms)
        key = bucket_key(
            backend, B, K, draws, dtype_name, has_key=has_key,
            factored=factored, devices=devices, transforms=transforms,
            sparse=sparse,
        )

        if mode != "off":
            hit = self.cache.get(key)
            if hit is not None and hit["method"] in cands:
                if not (mode == "measure" and hit.get("source") == "model"):
                    W = int(hit.get("W", 32))
                    tb0, tk0 = cost_model.default_tiles(B, K, W)
                    return Resolution(
                        method=hit["method"], W=W,
                        tb=int(hit.get("tb") or tb0),
                        tk=int(hit.get("tk") or tk0),
                        source=str(hit.get("source", "model")),
                    )

        dtype_bytes = 2 if "16" in dtype_name else 8 if "64" in dtype_name else 4
        if mode == "measure" and not _tracing_active():
            method, W, us = self._tune(
                cands, B, K, draws, dtype_name, dtype_bytes, backend,
                factored=factored, truncated=truncated, sparse=sparse,
            )
            source = "measured"
        else:
            method, W, us = cost_model.choose(
                cands, B, K, draws=draws, dtype_bytes=dtype_bytes,
                backend=backend, factored=factored, truncated=truncated,
                sparse=sparse, kd=kd,
            )
            source = "model"
        tb, tk = cost_model.default_tiles(B, K, W)
        if mode != "off":
            self.cache.put(key, method, W, us, source=source, tb=tb, tk=tk)
            self.cache.save_if_dirty()
        return Resolution(method=method, W=W, tb=tb, tk=tk, source=source)

    def _tune(self, cands, B, K, draws, dtype_name, dtype_bytes, backend,
              factored=False, truncated=False, sparse=False):
        """Time every candidate at the bucket's representative shape (the
        blocked methods at a small W sweep around the model's guess); fall
        back to the cost model if no candidate serves the workload."""
        import jax.numpy as jnp

        dtype = jnp.dtype(dtype_name)
        w_guess = cost_model.default_w(K)
        blocked = ("fenwick", "two_level", "butterfly", "kernel",
                   "kernel_trunc", "lda_kernel")
        best = None
        for method in cands:
            ws = sorted({w_guess, 32}) if method in blocked else (w_guess,)
            for W in ws:
                us = measure_method(method, B, K, W, dtype=dtype,
                                    factored=factored, truncated=truncated,
                                    sparse=sparse)
                if us is None:
                    continue
                if draws > 1 and method in cost_model.CACHED_TABLE_METHODS:
                    # measured time is build+1 draw; cross-call table reuse
                    # (dist_key) amortizes the build — scale by the cost
                    # model's own amortization ratio for this method
                    kw = dict(W=W, dtype_bytes=dtype_bytes, backend=backend)
                    full = cost_model.method_cost_eq(method, K, draws=1, **kw)
                    amort = cost_model.method_cost_eq(
                        method, K, draws=draws, **kw
                    )
                    us *= amort / full
                if best is None or us < best[0]:
                    best = (us, method, W)
        if best is None:
            method, W, us = cost_model.choose(
                cands, B, K, draws=draws, dtype_bytes=dtype_bytes,
                backend=backend, factored=factored, truncated=truncated,
                sparse=sparse,
            )
            return method, W, us
        us, method, W = best
        return method, W, us


# ---------------------------------------------------------------------------
# Process-global tuner (what sample_categorical(method="auto") consults)
# ---------------------------------------------------------------------------

_GLOBAL: Optional[Tuner] = None


def get_tuner() -> Tuner:
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = Tuner()
    return _GLOBAL


def reset_tuner() -> None:
    """Drop the global tuner (tests point $REPRO_AUTOTUNE_CACHE elsewhere
    and need the lazily-loaded cache re-read)."""
    global _GLOBAL
    _GLOBAL = None
