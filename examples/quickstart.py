"""Quickstart: draw from 100k distinct discrete distributions with the
butterfly-patterned partial-sums technique (Steele & Tristan 2015), and
verify the statistics.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import sampling
from repro.core import sample_categorical

B, K = 100_000, 200  # 100k samplers, 200 categories (paper's K>200 regime)
rng = np.random.default_rng(0)

# every row is its OWN unnormalized distribution (theta*phi products in LDA,
# vocab logits in LLM decode, mixture responsibilities, ...)
weights = jnp.array(rng.gamma(0.3, size=(B, K)).astype(np.float32))

key = jax.random.PRNGKey(42)

# -- the distribution-object API (primary) ---------------------------------
# plan once (the method is resolved here, not per draw), build the pytree
# Categorical once, draw from it as many times as you like
for method in ("butterfly", "fenwick", "two_level", "prefix", "gumbel"):
    p = sampling.plan(weights.shape, method=method, W=32)
    dist = p.build(weights)              # the paper's table, built once
    idx = p.draw(dist, key=key)
    idx.block_until_ready()
    print(f"{method:10s} -> drew {idx.shape[0]} samples, "
          f"first five: {np.asarray(idx[:5])}")

# -- frozen-distribution variants (DESIGN.md §11) --------------------------
# tables built ON DEVICE: refresh stays in-graph (no host callback), draws
# are O(1) (alias_device) or fixed-depth root-cached descent (radix_forest)
for method in ("alias_device", "radix_forest"):
    p = sampling.plan(weights.shape, method=method, draws=16)
    dist = p.build(weights)              # merged-rank pack / radix forest
    idx = p.draw(dist, key=key)
    idx.block_until_ready()
    print(f"{method:12s} -> drew {idx.shape[0]} samples, "
          f"first five: {np.asarray(idx[:5])}")

# what does "auto" pick for this draw-heavy frozen workload?
auto = sampling.plan(weights.shape, method="auto", draws=16)
print(f"auto (draws=16) resolved -> method={auto.table_method!r}")

# multi-draw reuses the SAME tables: 8 draws per row in one fused call,
# uniforms derived on device (zero table rebuilds — the paper's win)
p = sampling.plan(weights.shape, method="fenwick", W=32, draws=8)
dist = p.build(weights)
multi = p.draw(dist, key=key, num_samples=8)         # (8, B)
print(f"multi-draw  -> {multi.shape} from one build "
      f"(build_count={sampling.build_count()})")

# -- the legacy one-shot shim (still supported, byte-identical) ------------
legacy = sample_categorical(weights, key=key, method="fenwick", W=32)
assert np.array_equal(np.asarray(legacy), np.asarray(p.draw(dist, key=key)))

# sanity: empirical marginal of row 0 matches its distribution
reps = jnp.tile(weights[:1], (50_000, 1))
draws = np.asarray(sample_categorical(reps, key=key, method="butterfly", W=32))
emp = np.bincount(draws, minlength=K) / len(draws)
tgt = np.asarray(weights[0] / weights[0].sum())
print(f"max |empirical - target| over {K} categories: {np.abs(emp - tgt).max():.4f}")
