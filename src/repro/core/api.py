"""Legacy one-shot sampling entry points — now thin shims.

The primary API lives in :mod:`repro.sampling`: build a pytree
:class:`~repro.sampling.Categorical` once (``from_weights`` /
``from_logits``) and draw from it through a compiled
:class:`~repro.sampling.SamplerPlan` (``plan(...)`` resolves the method
once at plan time).  Migration::

    # before                                   # after
    sample_categorical(w, key=k,               p = sampling.plan(w.shape)
                       method="auto")          idx = p.sample(w, key=k)

    sample_categorical(w, u=u,                 p = sampling.plan(w.shape,
                       method="fenwick",                         method="fenwick", W=32)
                       W=32, dist_key="phi")   dist = p.build(w)      # hold it
                                               idx = p.draw(dist, u=u)
                                               dist = dist.refreshed(w2)  # w changed

    sample_from_logits(logits, k,              p = sampling.plan(logits.shape)
                       temperature=t)          tok = p.sample_logits(logits, k, temperature=t)

``sample_categorical(weights, key=..., method=...)`` remains supported
unchanged — it builds a throwaway ``Categorical`` + plan per call and is
byte-identical to the pre-redesign implementation for fixed
``(method, W, u)`` inputs.

Methods:
  * ``auto``      — ``sampling.plan``'s rule: the fused kernel on TPU,
                    ``two_level`` elsewhere, ``fenwick`` for a reused
                    table (the default everywhere a config doesn't say
                    otherwise)
  * ``butterfly`` — paper-faithful butterfly table + add/subtract walk
  * ``fenwick``   — TPU-adapted per-sample dyadic table (DESIGN.md §2)
  * ``two_level`` — fused two-pass draw: (B, K/W) block sums + one gathered
                    W-block per sample, no K-length table ever materializes
                    (the pure-XLA twin of the Pallas kernel)
  * ``kernel``    — fused tiled Pallas kernel (one pallas_call on TPU;
                    block selection in-kernel — DESIGN.md §3)
  * ``prefix``    — Alg. 1/3 full prefix sums + searchsorted (baseline)
  * ``gumbel``    — Gumbel-max one-pass baseline
  * ``alias``     — Walker/Vose alias tables (related-work baseline)
  * ``alias_device`` — split-based alias build on device (closed jaxpr,
                    rebuildable inside jit; O(1) draws)
  * ``radix_forest`` — radix-tree forest (cheap rebuild, fixed-depth
                    divergence-free draw — Binder & Keller 2019)

Factored workloads (weights as a theta-phi product — the LDA z-draw)
have their own zero-materialization path: build with
``repro.sampling.Categorical.from_factors`` (variant ``lda_kernel``) and
refresh with ``refresh_from_factors`` — never flatten the product just
to call this shim.

Repeated distributions: pass ``dist_key="..."`` (with ``draws=`` as a
reuse hint for ``auto``) and the alias/Fenwick state is memoized in
``repro.sampling.tables``' cache across calls.  The cache keys on a cheap
content digest of the weights, so silently changed weights rebuild
instead of serving a stale table; prefer holding a ``Categorical`` and
calling ``dist.refreshed(new_weights)`` explicitly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

METHODS = (
    "auto", "butterfly", "fenwick", "two_level", "kernel", "prefix",
    "gumbel", "alias", "alias_device", "radix_forest",
)

# the variants whose built state the table cache memoizes under dist_key
_CACHED_KINDS = ("alias", "fenwick", "alias_device", "radix_forest")


def sample_categorical(
    weights: jnp.ndarray,
    key: Optional[jax.Array] = None,
    u: Optional[jnp.ndarray] = None,
    method: str = "auto",
    W: Optional[int] = None,
    draws: int = 1,
    dist_key: Optional[str] = None,
) -> jnp.ndarray:
    """Draw one category index per row of ``weights``.

    Either ``key`` (PRNG key; uniforms are derived internally) or ``u``
    (precomputed uniforms, shape (B,)) must be given.  ``gumbel`` and
    ``alias`` require ``key``.

    ``method="auto"`` resolves through ``repro.sampling.plan`` (see module
    docstring); ``draws`` is the expected-uses-per-distribution hint
    (above 1 ``auto`` picks ``fenwick``), and ``dist_key`` enables
    cross-call table reuse for the alias/fenwick strategies.  The two go together: without
    a ``dist_key`` nothing is reused between calls, so ``auto`` ignores
    ``draws`` rather than select a method whose amortization would never
    materialize.
    """
    from repro import sampling

    weights = jnp.asarray(weights)
    if weights.ndim == 1:
        return sample_categorical(
            weights[None], key=key, u=u, method=method, W=W,
            draws=draws, dist_key=dist_key,
        )[0]
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; options: {METHODS}")
    eff_draws = draws if dist_key is not None else 1
    # caller-supplied uniforms must drive the draw: with u given, resolve
    # as key-less so auto never picks a method (gumbel/alias) that would
    # silently ignore u
    has_key = key is not None and u is None
    p = sampling.plan(
        weights.shape,
        method=method,
        W=W,
        dtype=str(weights.dtype),
        draws=eff_draws,
        has_key=has_key,
    )
    if p.method in ("gumbel", "alias", "alias_device") and key is None:
        raise ValueError(f"{p.method} requires a PRNG key")
    if u is None and key is None:
        raise ValueError("need key or u")
    if dist_key is not None and p.method in _CACHED_KINDS:
        from repro.sampling import tables

        dist = tables.get_table_cache().get_or_build_dist(dist_key, p, weights)
    else:
        dist = p.build(weights)
    if p.method in ("gumbel", "alias", "alias_device"):
        # key-driven variants consume PRNG state even when u was (also)
        # supplied — matching the pre-redesign dispatch order
        return p.draw(dist, key=key)
    return p.draw(dist, key=key, u=u)


def sample_from_logits(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: float = 1.0,
    method: str = "auto",
    W: Optional[int] = None,
) -> jnp.ndarray:
    """Serving-path helper: temperature sampling from (B, V) logits.

    Converts to stable unnormalized probabilities then draws with the
    requested strategy (greedy for temperature == 0).  ``method="auto"``
    resolves per (B, V) workload exactly like ``sample_categorical``
    (always at draws=1: decode logits change every step, so there is no
    distribution reuse to amortize).

    Float logits keep their dtype through the softmax — ``bfloat16``
    logits are NOT upcast, halving the softmax's HBM traffic, and the
    plan records the real dtype.
    """
    from repro import sampling

    logits = jnp.asarray(logits)
    if not jnp.issubdtype(logits.dtype, jnp.floating):
        logits = logits.astype(jnp.float32)
    if logits.ndim == 1:
        return sample_from_logits(
            logits[None], key, temperature=temperature, method=method, W=W
        )[0]
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    p = sampling.plan(
        logits.shape,
        method=method,
        W=W,
        dtype=str(logits.dtype),
        draws=1,
        has_key=True,
    )
    return p.sample_logits(logits, key, temperature=temperature)
