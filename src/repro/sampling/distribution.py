"""``Categorical`` — the paper's reusable table as a first-class pytree.

The butterfly-patterned partial-sum table (and its siblings: the Fenwick
table, two-level block sums, alias prob/alias arrays, plain prefix sums)
is built once from a weight matrix and searched per draw.  This module
makes the *built structure* the object the rest of the system passes
around: a :class:`Categorical` is a registered JAX pytree whose leaves are
exactly that precomputed state, so a built distribution can be

* closed over inside ``jax.jit`` (zero table rebuilds across calls — the
  leaves are ordinary arrays, never recomputed at trace time),
* ``jax.vmap``-ed over a batch of distributions (stack the leaves),
* donated, sharded, or checkpointed like any other pytree.

Static metadata (variant name, block width W, the unpadded (B, K) shape)
travels in the treedef, so a jitted draw specializes per variant/shape the
way the old string-dispatch path specialized per ``method=`` argument.

Variants and their state leaves:

  ==========  =====================================================
  method      state
  ==========  =====================================================
  prefix      ``prefix``  (B, K) inclusive prefix sums
  fenwick     ``table``   (B, Kp) per-sample dyadic segment table
  butterfly   ``table``   (G, nb, W, W) paper-faithful butterfly table
  two_level   ``blocks``  (B, nb, W) padded weight blocks,
              ``running`` (B, nb) running block sums
  kernel      ``weights`` (Bp, Kp) padded weights,
              ``running`` (Bp, Kp/W) running block sums (Pallas pass A)
  lda_kernel  ``theta`` (C, Kp) / ``phi`` (V, Kp) padded factors,
              ``doc_ids``/``words`` (B,) row selectors,
              ``running`` (B, Kp/W) factored-pass-A running block sums
              — the (B, K) weight product never materializes
  gumbel      ``logw``    (B, K) masked log-weights
  alias       ``prob``/``alias``  (B, K) Walker/Vose tables
  alias_device  ``prob``/``alias``  (B, K) — same draw contract as
              ``alias`` but built ON DEVICE by the split-based PSA
              builder (``repro.kernels.alias_build``): the build is a
              closed jaxpr, so ``refreshed()`` and the sparse-LDA sweep
              rebuild tables in-graph with no host round-trip
  radix_forest  ``cdf`` (B, K) normalized prefix sums,
              ``root`` (B, M+1) radix-forest root ranges (Binder &
              Keller) — divergence-free fixed-depth draw, cumsum-cheap
              rebuild
  ==========  =====================================================

Numerics are bit-identical to the pre-redesign one-shot paths: every
builder/draw pair is the same op sequence ``repro.core`` always ran,
split at the table boundary (``tests/test_sampling_api.py`` pins this).

``BUILD_COUNT`` (via :func:`build_count`) increments on every table
build — tests assert a jit-closed distribution draws repeatedly with the
counter frozen, i.e. genuinely zero rebuilds.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import alias as _alias
from repro.core import butterfly as _bfly

# every variant a Categorical can carry state for (== repro.core.METHODS
# minus the "auto" placeholder, which resolves before a build).
# "lda_kernel" is the *factored* variant: its state is the (theta, phi,
# words, doc_ids) factorization plus factored-pass-A running block sums —
# the (B, K) weight product never materializes (DESIGN.md §4); build it
# via :meth:`Categorical.from_factors` / :meth:`refresh_from_factors`.
VARIANTS = (
    "prefix", "fenwick", "butterfly", "two_level", "kernel", "gumbel",
    "alias", "lda_kernel", "alias_device", "radix_forest",
)

# variants built from a factorization instead of a flat weight matrix
FACTORED_VARIANTS = ("lda_kernel",)

# u-driven variants draw from a caller-supplied (or key-derived) uniform;
# key-driven ones consume PRNG state directly
U_VARIANTS = (
    "prefix", "fenwick", "butterfly", "two_level", "kernel", "lda_kernel",
    "radix_forest",
)
KEY_VARIANTS = ("gumbel", "alias", "alias_device")

# table builds since process start — the "zero rebuilds" witness.  A build
# inside a jit trace increments exactly once (at trace time); executing
# the compiled function again does not.
_BUILD_COUNT = 0


def build_count() -> int:
    return _BUILD_COUNT


def _float_like(weights: jnp.ndarray) -> jnp.ndarray:
    """The dtype normalization every pre-redesign draw path applied."""
    if weights.dtype not in (jnp.float32, jnp.float64):
        return weights.astype(jnp.float32)
    return weights


# ---------------------------------------------------------------------------
# State builders (one per variant; op-identical to the legacy draw preludes)
# ---------------------------------------------------------------------------


def _build_state(method: str, weights: jnp.ndarray, W: int) -> Dict[str, Any]:
    if method == "prefix":
        return {"prefix": jnp.cumsum(_float_like(weights), axis=-1)}
    if method == "fenwick":
        wp, _, _ = _bfly._prep(weights, W, group_pad=False)
        return {"table": _bfly.build_fenwick_table(wp, W)}
    if method == "butterfly":
        wp, _, _ = _bfly._prep(weights, W, group_pad=True)
        return {"table": _bfly.build_butterfly_table(wp, W)}
    if method == "two_level":
        wp, _, _ = _bfly._prep(weights, W, group_pad=False)
        B = wp.shape[0]
        nb = wp.shape[1] // W
        blocks = wp.reshape(B, nb, W)
        running = jnp.cumsum(blocks.sum(axis=-1), axis=1)
        return {"blocks": blocks, "running": running}
    if method == "kernel":
        from repro.kernels.butterfly_sample import ops as _kops

        wp, running = _kops.build_block_sums(weights, W=W)
        return {"weights": wp, "running": running}
    if method == "lda_kernel":
        raise ValueError(
            "the factored 'lda_kernel' variant builds from (theta, phi, "
            "words) — use Categorical.from_factors / refresh_from_factors"
        )
    if method == "gumbel":
        wf = _float_like(weights)
        logw = jnp.log(jnp.maximum(wf, jnp.finfo(wf.dtype).tiny))
        return {"logw": jnp.where(wf > 0, logw, -jnp.inf)}
    if method == "alias":
        tables = _alias.build_alias_tables(weights)
        return {"prob": tables.prob, "alias": tables.alias}
    if method == "alias_device":
        from repro.kernels.alias_build import build_alias_tables_device

        tables = build_alias_tables_device(weights)
        return {"prob": tables.prob, "alias": tables.alias}
    if method == "radix_forest":
        from repro.core import radix as _radix

        cdf, root = _radix.build_radix_forest(weights)
        return {"cdf": cdf, "root": root}
    raise ValueError(f"unknown Categorical variant {method!r}; options: {VARIANTS}")


# table construction runs as ONE fused dispatch per (method, W, shape)
# instead of eager op-by-op; the alias builder's lax.while_loop needs the
# jit anyway.  The build counter increments in the host wrapper so a
# compiled-executable replay never counts as a rebuild.
_build_state_jit = jax.jit(_build_state, static_argnames=("method", "W"))


def _note_build() -> None:
    """Count one table build.  The sharded build path
    (``repro.sampling.sharded``) constructs its state through shard_map
    rather than ``_counted_build`` and bumps the counter here, so the
    zero-rebuilds witness covers mesh-sharded distributions too."""
    global _BUILD_COUNT
    _BUILD_COUNT += 1


def _counted_build(method: str, weights: jnp.ndarray, W: int) -> Dict[str, Any]:
    _note_build()
    return _build_state_jit(method, weights, W)


def _counted_build_factored(theta, phi, doc_ids, words, W: int, tb: int):
    """Factored table build (lda_kernel variant): pass A runs straight on
    the (theta, phi) factors — no (B, K) weight tensor, on any backend."""
    _note_build()
    from repro.kernels.lda_draw import ops as _lops

    thetap, phip, running = _lops.lda_build_running(
        theta, phi, doc_ids, words, W=W, tb=tb or 8
    )
    return {
        "theta": thetap,
        "phi": phip,
        "doc_ids": doc_ids,
        "words": words,
        "running": running,
    }


# ---------------------------------------------------------------------------
# The pytree distribution object
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Categorical:
    """A batch of categorical distributions with precomputed draw state.

    ``method``/``W``/``shape`` are static (treedef) metadata; ``state``
    holds the variant's table leaves.  Construct via :meth:`from_weights`
    or :meth:`from_logits`; rebuild for new weights with :meth:`refreshed`.
    """

    method: str
    W: int
    shape: Tuple[int, int]          # unpadded (B, K)
    state: Dict[str, Any]
    tb: int = 0                     # draw-side row tile (0 = kernel default)

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_weights(
        cls,
        weights,
        method: str = "auto",
        W: Optional[int] = None,
        draws: int = 1,
    ) -> "Categorical":
        """Build a distribution from (B, K) non-negative weights.

        ``method="auto"`` resolves through a memoized
        :func:`repro.sampling.plan` (its rule runs once per
        (shape, dtype, backend)); a concrete method skips resolution.
        ``W=None``/0 picks W ~ sqrt(K) (``kernels.runtime.default_w``).
        """
        weights = jnp.asarray(weights)
        if weights.ndim != 2:
            raise ValueError(f"weights must be (B, K), got shape {weights.shape}")
        from repro.sampling.plan import plan

        p = plan(
            weights.shape,
            method=method,
            W=W,
            dtype=str(weights.dtype),
            draws=draws,
            has_key=method in KEY_VARIANTS or method == "auto",
        )
        return cls._build(weights, p.method, p.W)

    @classmethod
    def from_logits(
        cls,
        logits,
        temperature: float = 1.0,
        method: str = "auto",
        W: Optional[int] = None,
        draws: int = 1,
        transforms=None,
    ) -> "Categorical":
        """Build from (B, V) logits via a temperature-scaled stable softmax.

        The softmax runs in the logits' own floating dtype — ``bfloat16``
        logits stay ``bfloat16`` through ``exp`` (halving HBM traffic) and
        the plan records the real dtype; individual builders upcast later
        where accumulation accuracy requires it.

        ``transforms`` is a truncation chain from
        :mod:`repro.sampling.transforms` (``TopK``/``TopP``/``MinP``,
        ``Temperature`` folded into the softmax): the truncated tokens'
        weights are zeroed *before* the table build, so every variant's
        precomputed state encodes the truncated distribution and every
        subsequent draw honors it for free (zero weights are never
        selected).
        """
        if transforms:
            from repro.sampling import transforms as _tr

            weights = _tr.apply_to_logits(transforms, logits, temperature)
        else:
            weights = logits_to_weights(logits, temperature)
        return cls.from_weights(weights, method=method, W=W, draws=draws)

    @classmethod
    def from_factors(
        cls,
        theta,
        phi,
        words,
        doc_ids=None,
        method: str = "lda_kernel",
        W: Optional[int] = None,
        tb: Optional[int] = None,
    ) -> "Categorical":
        """Build a factored distribution: sample s draws from the product
        ``theta[doc_ids[s], :] * phi[words[s], :]``.

        The paper's LDA setting (Alg. 8): the block-sum table is built
        *directly from the factored form* — the (B, K) flat weight matrix
        never exists.  ``doc_ids=None`` means one theta row per sample.
        ``method="auto"`` resolves through a factored-workload plan; if
        that resolves to a flat-weight variant (tiny K, a measured
        winner), the product is materialized once and the flat table
        built — same behavior as ``SamplerPlan.build_from_factors``.
        """
        theta = jnp.asarray(theta)
        phi = jnp.asarray(phi)
        words = jnp.asarray(words, jnp.int32)
        B = int(words.shape[0])
        K = int(theta.shape[1])
        if doc_ids is None:
            if theta.shape[0] != B:
                raise ValueError(
                    f"doc_ids=None needs one theta row per sample; got "
                    f"theta {theta.shape} for {B} samples"
                )
            doc_ids = jnp.arange(B, dtype=jnp.int32)
        doc_ids = jnp.asarray(doc_ids, jnp.int32)
        from repro.sampling.plan import plan

        p = plan(
            (B, K), method=method, W=W, dtype=str(theta.dtype),
            has_key=False, factored=True,
        )
        if p.method not in FACTORED_VARIANTS:
            flat = theta[doc_ids] * phi[words]
            return cls._build(flat, p.method, p.W, tb or p.tb)
        return cls._build_factored(
            theta, phi, doc_ids, words, p.method, p.W, tb or p.tb
        )

    @classmethod
    def _build(cls, weights, method: str, W: int, tb: int = 0) -> "Categorical":
        weights = jnp.asarray(weights)
        return cls(
            method=method,
            W=int(W),
            shape=(int(weights.shape[0]), int(weights.shape[1])),
            state=_counted_build(method, weights, W),
            tb=int(tb),
        )

    @classmethod
    def _build_factored(
        cls, theta, phi, doc_ids, words, method: str, W: int, tb: int = 0
    ) -> "Categorical":
        return cls(
            method=method,
            W=int(W),
            shape=(int(words.shape[0]), int(theta.shape[1])),
            state=_counted_build_factored(theta, phi, doc_ids, words, W, tb),
            tb=int(tb),
        )

    def refreshed(self, weights) -> "Categorical":
        """Rebuild this distribution's tables from new same-shape weights.

        The explicit answer to the stale-table footgun: when the
        underlying weights change (an LDA phi resample, an updated unigram
        table), call ``dist.refreshed(new_weights)`` — same variant, same
        W, fresh leaves."""
        if self.method in FACTORED_VARIANTS:
            raise ValueError(
                f"{self.method!r} is a factored variant; refresh it with "
                "refresh_from_factors(theta, phi) instead of flat weights"
            )
        weights = jnp.asarray(weights)
        if tuple(weights.shape) != self.shape:
            raise ValueError(
                f"refreshed() weights shape {weights.shape} != {self.shape}; "
                "build a new Categorical for a different shape"
            )
        return Categorical._build(weights, self.method, self.W, self.tb)

    def refresh_from_factors(self, theta, phi, words=None) -> "Categorical":
        """Rebuild a factored distribution's block-sum table from new
        factors (an LDA sweep's resampled theta/phi) — same variant, same
        W, same word positions (pass new ``words`` to retarget), and still
        no (B, K) weight materialization."""
        if self.method not in FACTORED_VARIANTS:
            raise ValueError(
                f"{self.method!r} carries flat-weight state; use "
                "refreshed(new_weights)"
            )
        theta = jnp.asarray(theta)
        phi = jnp.asarray(phi)
        words = (
            self.state["words"] if words is None
            else jnp.asarray(words, jnp.int32)
        )
        if int(theta.shape[1]) != self.shape[1]:
            raise ValueError(
                f"refresh_from_factors() K={theta.shape[1]} != {self.shape[1]}"
            )
        if int(words.shape[0]) != self.shape[0]:
            raise ValueError(
                f"refresh_from_factors() got {words.shape[0]} samples, "
                f"expected {self.shape[0]}"
            )
        return Categorical._build_factored(
            theta, phi, self.state["doc_ids"], words, self.method, self.W, self.tb
        )

    # -- introspection -----------------------------------------------------

    @property
    def batch_size(self) -> int:
        return self.shape[0]

    @property
    def num_categories(self) -> int:
        return self.shape[1]

    @property
    def needs_key(self) -> bool:
        return self.method in KEY_VARIANTS

    # -- drawing -----------------------------------------------------------

    def draw(
        self,
        key: Optional[jax.Array] = None,
        u: Optional[jnp.ndarray] = None,
        num_samples: int = 1,
    ) -> jnp.ndarray:
        """Draw indices; see :func:`draw` (module level) for semantics."""
        return draw(self, key=key, u=u, num_samples=num_samples)


def _cat_flatten(d: Categorical):
    keys = tuple(sorted(d.state))
    return tuple(d.state[k] for k in keys), (d.method, d.W, d.shape, keys, d.tb)


def _cat_unflatten(aux, children) -> Categorical:
    method, W, shape, keys, tb = aux
    return Categorical(
        method=method, W=W, shape=shape, state=dict(zip(keys, children)), tb=tb
    )


jax.tree_util.register_pytree_node(Categorical, _cat_flatten, _cat_unflatten)


# ---------------------------------------------------------------------------
# Logits -> weights (dtype-preserving stable softmax)
# ---------------------------------------------------------------------------


def logits_to_weights(logits, temperature: float = 1.0) -> jnp.ndarray:
    """Temperature-scaled unnormalized probabilities from (B, V) logits.

    Stable (max-subtracted) and dtype-preserving: float inputs keep their
    dtype (bfloat16 in, bfloat16 out); non-float inputs upcast to float32.
    ``temperature`` may be a scalar or a per-row (B,) array (per-request
    temperature — a traced operand, so one executable serves any mix).
    """
    logits = jnp.asarray(logits)
    if not jnp.issubdtype(logits.dtype, jnp.floating):
        logits = logits.astype(jnp.float32)
    t = jnp.asarray(temperature)
    z = logits / (t[:, None] if t.ndim == 1 else t)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    return jnp.exp(z)


# ---------------------------------------------------------------------------
# Draw kernels (pure functions of (dist, u | key) — jit/vmap composable)
# ---------------------------------------------------------------------------


def _draw_with_u(dist: Categorical, u: jnp.ndarray) -> jnp.ndarray:
    """One draw per row from a caller-supplied (B,) uniform vector."""
    method, W = dist.method, dist.W
    B, K = dist.shape
    if method == "prefix":
        p = dist.state["prefix"]
        stop = p[:, -1] * u.astype(p.dtype)
        idx = jax.vmap(lambda row, s: jnp.searchsorted(row, s, side="right"))(
            p, stop
        )
        return jnp.minimum(idx, K - 1).astype(jnp.int32)
    if method == "fenwick":
        return _bfly.draw_fenwick_from_table(dist.state["table"], u, W=W, K=K)
    if method == "butterfly":
        table = dist.state["table"]
        G = table.shape[0]
        totals = table[:, -1, W - 1, :]                       # (G, W)
        up, _ = _bfly.pad_to_multiple(
            u.astype(table.dtype), axis=0, mult=W, value=0.5
        )
        stop = totals * up.reshape(G, W)
        idx = _bfly.butterfly_search(table, stop, W).reshape(-1)[:B]
        return jnp.minimum(idx, K - 1)
    if method == "two_level":
        blocks, running = dist.state["blocks"], dist.state["running"]
        nb = running.shape[1]
        totals = running[:, -1]
        stop = totals * u.astype(blocks.dtype)
        jb = jnp.clip(
            jnp.sum(running <= stop[:, None], axis=1).astype(jnp.int32), 0, nb - 1
        )
        lo = jnp.where(
            jb > 0,
            jnp.take_along_axis(
                running, jnp.maximum(jb - 1, 0)[:, None], axis=1
            )[:, 0],
            jnp.zeros_like(stop),
        )
        sel = jnp.take_along_axis(blocks, jb[:, None, None], axis=1)[:, 0]
        prefix = jnp.cumsum(sel, axis=-1) + lo[:, None]
        r = jnp.sum(prefix <= stop[:, None], axis=1).astype(jnp.int32)
        idx = jb * W + jnp.minimum(r, W - 1)
        return jnp.minimum(idx, K - 1)
    if method == "kernel":
        from repro.kernels.butterfly_sample import ops as _kops

        kw = {"tb": dist.tb} if dist.tb else {}
        return _kops.butterfly_sample_from_sums(
            dist.state["weights"], dist.state["running"], u, K=K, W=W, **kw
        )
    if method == "lda_kernel":
        from repro.kernels.lda_draw import ops as _lops

        return _lops.lda_draw_from_running(
            dist.state["theta"], dist.state["phi"], dist.state["running"],
            u, dist.state["doc_ids"], dist.state["words"],
            K=K, W=W, tb=dist.tb or 8,
        )
    if method == "radix_forest":
        from repro.core import radix as _radix

        return _radix.draw_radix_forest(
            dist.state["cdf"], dist.state["root"], u
        )
    raise ValueError(
        f"variant {method!r} draws from PRNG keys, not uniforms — pass key="
    )


def _draw_with_key(dist: Categorical, key: jax.Array) -> jnp.ndarray:
    """One draw per row from a PRNG key."""
    method = dist.method
    if method == "gumbel":
        logw = dist.state["logw"]
        g = jax.random.gumbel(key, logw.shape, dtype=logw.dtype)
        return jnp.argmax(logw + g, axis=-1).astype(jnp.int32)
    if method in ("alias", "alias_device"):
        tables = _alias.AliasTable(prob=dist.state["prob"], alias=dist.state["alias"])
        return _alias.draw_alias_batch(tables, key)
    # u-driven variant: derive the uniforms device-side, exactly as the
    # legacy sample_categorical(key=...) path did
    u = jax.random.uniform(key, (dist.shape[0],), dtype=jnp.float32)
    return _draw_with_u(dist, u)


def _draw_impl(
    dist: Categorical,
    key: Optional[jax.Array],
    u: Optional[jnp.ndarray],
    num_samples: int,
) -> jnp.ndarray:
    if u is not None:
        u = jnp.asarray(u)
        if u.ndim == 2:
            if dist.method in ("kernel", "lda_kernel"):
                # the tiled pass B takes the whole (S, B) uniform matrix
                # in ONE kernel launch (rows indirection) — no vmap
                return _draw_with_u(dist, u)
            return jax.vmap(lambda uu: _draw_with_u(dist, uu))(u)
        out = _draw_with_u(dist, u)
        if num_samples != 1:
            raise ValueError("num_samples > 1 needs u of shape (S, B) or a key")
        return out
    if key is None:
        raise ValueError("draw needs key= or u=")
    if num_samples == 1:
        return _draw_with_key(dist, key)
    # multi-draw: ALL randomness derived device-side in one shot — no
    # host round-trip per draw
    if dist.method in KEY_VARIANTS:
        keys = jax.random.split(key, num_samples)
        return jax.vmap(lambda k: _draw_with_key(dist, k))(keys)
    us = jax.random.uniform(
        key, (num_samples, dist.shape[0]), dtype=jnp.float32
    )
    if dist.method in ("kernel", "lda_kernel"):
        return _draw_with_u(dist, us)
    return jax.vmap(lambda uu: _draw_with_u(dist, uu))(us)


# the jitted entry points: Categorical flattens into (leaves, static aux),
# so jit specializes per (variant, W, shape) and caches the executable —
# repeated draws from one built distribution never rebuild its tables
_draw_key_jit = jax.jit(
    lambda dist, key, num_samples: _draw_impl(dist, key, None, num_samples),
    static_argnames=("num_samples",),
)
_draw_u_jit = jax.jit(
    lambda dist, u, num_samples: _draw_impl(dist, None, u, num_samples),
    static_argnames=("num_samples",),
)


def draw(
    dist: Categorical,
    key: Optional[jax.Array] = None,
    u: Optional[jnp.ndarray] = None,
    num_samples: int = 1,
) -> jnp.ndarray:
    """Draw category indices from a built :class:`Categorical`.

    * ``u=`` (shape (B,) or (num_samples, B)): the u-driven variants draw
      deterministically from the given uniforms.
    * ``key=``: uniforms (or Gumbel noise / alias coordinates) are derived
      device-side.  ``num_samples > 1`` returns (num_samples, B) with all
      randomness derived in one fused computation.

    Inside a ``jax.jit`` trace this composes as a nested jitted call; the
    distribution's tables are ordinary pytree leaves, never rebuilt.
    """
    if u is not None:
        return _draw_u_jit(dist, jnp.asarray(u), num_samples=num_samples)
    return _draw_key_jit(dist, key, num_samples=num_samples)
