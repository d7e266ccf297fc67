"""Distribution-object sampling API — the primary way to draw.

The paper's central artifact is a *reusable table* built once from a
weight matrix and searched per draw.  This package gives that artifact a
first-class API:

* :class:`Categorical` — a registered pytree distribution whose leaves
  are the precomputed draw state (butterfly/Fenwick tables, two-level
  block sums, alias arrays, prefix sums).  Build it with
  ``Categorical.from_weights`` / ``Categorical.from_logits``, pass it
  through ``jit``/``vmap``/shardings freely, refresh it with
  ``dist.refreshed(new_weights)`` when the weights change.
* :class:`SamplerPlan` — the compiled side, from :func:`plan`, which
  resolves the method once at plan time and exposes jitted
  ``build`` / ``draw`` / ``sample`` / ``sample_logits``.

``repro.core.sample_categorical`` / ``sample_from_logits`` remain as
compatibility shims over this package (byte-identical draws for fixed
``(method, W, u)``); new code should plan once and draw many::

    from repro import sampling

    p = sampling.plan(weights.shape, method="auto", draws=16)
    dist = p.build(weights)                  # tables built exactly once
    idx = p.draw(dist, key=key, num_samples=16)   # (16, B) draws

Multi-device batches pass a mesh — the same plan API, shard_map'd tiled
kernels per shard, counter RNG instead of uniform buffers, zero
collectives on the draw path (:mod:`repro.sampling.sharded`)::

    p = sampling.plan((B, V), mesh=mesh)     # resolves the per-shard shape
    tok = p.sample_logits(logits, key)       # logits row-sharded over mesh
"""

from repro.sampling.distribution import (
    FACTORED_VARIANTS,
    KEY_VARIANTS,
    U_VARIANTS,
    VARIANTS,
    Categorical,
    build_count,
    draw,
    logits_to_weights,
)
from repro.sampling.plan import (
    SamplerPlan,
    plan,
    plan_stats,
    reset_plans,
)
from repro.sampling import sharded
from repro.sampling import transforms
from repro.sampling.transforms import MinP, Temperature, TopK, TopP

__all__ = [
    "Categorical",
    "FACTORED_VARIANTS",
    "KEY_VARIANTS",
    "MinP",
    "SamplerPlan",
    "Temperature",
    "TopK",
    "TopP",
    "U_VARIANTS",
    "VARIANTS",
    "build_count",
    "draw",
    "logits_to_weights",
    "plan",
    "plan_stats",
    "reset_plans",
    "sharded",
    "transforms",
]
