"""Distribution-object sampling API: pytree Categorical + SamplerPlan.

Pins the redesign's contracts:
  * every Categorical variant is a registered pytree (flatten/unflatten,
    jit-closure, vmap over a batch of distributions) with ZERO table
    rebuilds once built,
  * plan() resolves method="auto" exactly once per (shape, dtype,
    backend) workload, by one rule that reads no environment variable
    and writes no file; the table of its answers is pinned literally,
  * the sample_categorical / sample_from_logits shims stay byte-identical
    to the pre-redesign one-shot implementations for fixed (method, W, u),
  * the dist_key table cache keys on weight content, so changed weights
    can never serve a stale table,
  * bfloat16 logits survive the stable-softmax path un-upcast.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import sampling
from repro.core import alias as _alias
from repro.core import butterfly as _bfly
from repro.core import gumbel as _gumbel
from repro.core import reference as _ref
from repro.core import sample_categorical, sample_from_logits
from repro.kernels import runtime
from repro.sampling import tables

from test_sampler_stats import CHI2_999, _chi2_stat

U_METHODS = ("prefix", "fenwick", "butterfly", "two_level", "kernel")
ALL_METHODS = U_METHODS + ("gumbel", "alias")

B, K, W = 16, 48, 8


@pytest.fixture
def weights():
    rng = np.random.default_rng(7)
    return jnp.asarray(rng.uniform(0.1, 1.0, (B, K)), jnp.float32)


@pytest.fixture
def uniforms():
    rng = np.random.default_rng(11)
    return jnp.asarray(rng.uniform(0.0, 1.0, (B,)), jnp.float32)


def legacy_draw(method, w, u, key):
    """The pre-redesign implementation of each strategy, verbatim."""
    if method == "prefix":
        return _ref.draw_prefix(w, u)
    if method == "fenwick":
        return _bfly.draw_fenwick(w, u, W=W)
    if method == "butterfly":
        return _bfly.draw_butterfly(w, u, W=W)
    if method == "two_level":
        return _bfly.draw_two_level(w, u, W=W)
    if method == "kernel":
        from repro.kernels.butterfly_sample import ops as _kops

        return _kops.butterfly_sample(w, u, W=W)
    if method == "gumbel":
        return _gumbel.draw_gumbel(w, key)
    if method == "alias":
        return _alias.draw_alias_batch(_alias.build_alias_tables(w), key)
    raise AssertionError(method)


# ---------------------------------------------------------------------------
# Pytree round-trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ALL_METHODS)
def test_pytree_roundtrip(method, weights, uniforms):
    dist = sampling.Categorical.from_weights(weights, method=method, W=W)
    leaves, treedef = jax.tree_util.tree_flatten(dist)
    assert leaves, f"{method}: no state leaves"
    dist2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert dist2.method == dist.method and dist2.W == dist.W
    assert dist2.shape == (B, K)
    key = jax.random.PRNGKey(3)
    a = np.asarray(sampling.draw(dist, key=key))
    b = np.asarray(sampling.draw(dist2, key=key))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("method", ALL_METHODS)
def test_jit_closure_zero_rebuilds(method, weights):
    """A built distribution closed over inside jit draws repeatedly with
    zero table rebuilds (the acceptance criterion's counter assert)."""
    dist = sampling.Categorical.from_weights(weights, method=method, W=W)
    n0 = sampling.build_count()
    f = jax.jit(lambda k: sampling.draw(dist, key=k))
    r1 = f(jax.random.PRNGKey(0))
    r2 = f(jax.random.PRNGKey(1))
    assert r1.shape == (B,) and r2.shape == (B,)
    assert sampling.build_count() == n0, f"{method}: tables were rebuilt"


@pytest.mark.parametrize("method", ["prefix", "fenwick", "two_level", "butterfly"])
def test_vmap_over_batch_of_distributions(method):
    """Stacked Categoricals vmap like any pytree: one draw per
    distribution-batch element, matching the unbatched draws."""
    rng = np.random.default_rng(5)
    ws = jnp.asarray(rng.uniform(0.1, 1.0, (4, B, K)), jnp.float32)
    us = jnp.asarray(rng.uniform(0.0, 1.0, (4, B)), jnp.float32)
    build = lambda w: sampling.Categorical.from_weights(w, method=method, W=W)
    stacked = jax.vmap(build)(ws)
    out = jax.vmap(lambda d, u: sampling.draw(d, u=u))(stacked, us)
    assert out.shape == (4, B)
    for i in range(4):
        exp = sampling.draw(build(ws[i]), u=us[i])
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(exp))


def test_refreshed_rebuilds_for_new_weights(weights, uniforms):
    rng = np.random.default_rng(13)
    w2 = jnp.asarray(rng.uniform(0.1, 1.0, (B, K)), jnp.float32)
    dist = sampling.Categorical.from_weights(weights, method="fenwick", W=W)
    fresh = dist.refreshed(w2)
    assert fresh.method == "fenwick" and fresh.W == W
    exp = sampling.Categorical.from_weights(w2, method="fenwick", W=W)
    np.testing.assert_array_equal(
        np.asarray(sampling.draw(fresh, u=uniforms)),
        np.asarray(sampling.draw(exp, u=uniforms)),
    )
    with pytest.raises(ValueError):
        dist.refreshed(w2[:, : K // 2])


# ---------------------------------------------------------------------------
# SamplerPlan: resolve-once + multi-draw
# ---------------------------------------------------------------------------


def test_plan_resolves_auto_once():
    sampling.reset_plans()
    s0 = sampling.plan_stats()["auto_resolves"]
    p1 = sampling.plan((64, 512), method="auto")
    assert sampling.plan_stats()["auto_resolves"] == s0 + 1
    # same workload: memoized, NOT re-resolved
    p2 = sampling.plan((64, 512), method="auto")
    assert p2 is p1
    assert sampling.plan_stats()["auto_resolves"] == s0 + 1
    # different (shape, dtype) workloads resolve independently, once each
    sampling.plan((64, 1024), method="auto")
    sampling.plan((64, 512), method="auto", dtype="bfloat16")
    assert sampling.plan_stats()["auto_resolves"] == s0 + 3
    # drawing through a plan never resolves again
    w = jnp.ones((64, 512), jnp.float32)
    p1.sample(w, key=jax.random.PRNGKey(0))
    p1.sample(w, key=jax.random.PRNGKey(1))
    assert sampling.plan_stats()["auto_resolves"] == s0 + 3


def test_plan_concrete_method_skips_auto_rule(weights):
    s0 = sampling.plan_stats()["auto_resolves"]
    p = sampling.plan(weights.shape, method="two_level", W=W)
    assert p.method == "two_level" and p.W == W
    assert sampling.plan_stats()["auto_resolves"] == s0


# ---------------------------------------------------------------------------
# The rule behind method="auto"
# ---------------------------------------------------------------------------

# what each kind of draw passes to plan()
AUTO_KINDS = {
    "flat": dict(has_key=False),
    "flat_key": dict(has_key=True),
    "factored": dict(has_key=False, factored=True),
    "trunc_kpm": dict(has_key=False, transforms="kpm"),
    "flat_draws16": dict(has_key=False, draws=16),
}
AUTO_METHOD = {
    ("tpu", "flat"): "kernel",
    ("tpu", "flat_key"): "kernel",
    ("tpu", "factored"): "lda_kernel",
    ("tpu", "trunc_kpm"): "kernel_trunc",
    ("tpu", "flat_draws16"): "fenwick",
    ("cpu", "flat"): "two_level",
    ("cpu", "flat_key"): "two_level",
    ("cpu", "factored"): "lda_kernel",
    ("cpu", "trunc_kpm"): "two_level",
    ("cpu", "flat_draws16"): "fenwick",
}
# K -> (W, tb, tk) at B=16
AUTO_LAUNCH = {
    64: (8, 8, 64),
    240: (16, 8, 240),
    1024: (32, 8, 512),
    4096: (64, 8, 512),
    151936: (128, 8, 512),
}
# Every draws=1 row above is what the cost model that preceded the rule
# answered for the same call.  The rule departs from it only where no
# benchmark cell runs: at K <= 16 (pinned below), and for draws > 1 below
# vocabulary scale, where the cost model picked radix_forest.
AUTO_CASES = [
    ((16, K), backend, kind, (AUTO_METHOD[backend, kind],) + AUTO_LAUNCH[K])
    for backend in ("tpu", "cpu")
    for kind in AUTO_KINDS
    for K in AUTO_LAUNCH
] + [
    # the cost model picked radix_forest and alias_device here
    ((16, 16), "tpu", "flat", ("kernel", 8, 8, 16)),
    ((16, 16), "cpu", "flat_key", ("two_level", 8, 8, 16)),
    # the moonlight-16b-a3b.batch decode draw: 64 slots x 163840
    ((64, 163840), "tpu", "flat", ("kernel", 128, 8, 512)),
]


def _auto(shape, backend, kind):
    p = sampling.plan(shape, method="auto", backend=backend, **AUTO_KINDS[kind])
    return (p.method, p.W, p.tb, p.tk)


@pytest.mark.parametrize(
    "shape,backend,kind,expected", AUTO_CASES,
    ids=[f"{b}-{k}-{s[0]}x{s[1]}" for s, b, k, _ in AUTO_CASES],
)
def test_auto_rule(shape, backend, kind, expected):
    assert _auto(shape, backend, kind) == expected


def test_plan_reads_no_environment_and_writes_no_file(tmp_path, monkeypatch):
    """The resolution is a function of the call alone: neither a home
    directory nor the variables that once steered a tuner change it, and
    planning leaves nothing on disk."""
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
    sampling.reset_plans()
    for shape, backend, kind, expected in AUTO_CASES:
        assert _auto(shape, backend, kind) == expected
    assert list(tmp_path.iterdir()) == []


def test_default_w_powers_of_two():
    for K in (2, 16, 200, 1024, 50_000, 10**6):
        W = runtime.default_w(K)
        assert 8 <= W <= 128 and (W & (W - 1)) == 0


def test_auto_statistically_matches_prefix():
    """auto must draw from the same distribution as the prefix oracle
    (chi-square on a skewed pmf, same gate as test_sampler_stats)."""
    K, N = 20, 150_000
    rng = np.random.default_rng(5)
    probs = rng.dirichlet(np.full(K, 0.3))
    w = jnp.tile(jnp.array(probs, jnp.float32)[None], (N, 1))
    for method in ("auto", "prefix"):
        idx = np.array(
            sample_categorical(w, key=jax.random.PRNGKey(1), method=method)
        )
        counts = np.bincount(idx, minlength=K).astype(np.float64)
        stat, _ = _chi2_stat(counts, probs)
        assert stat < CHI2_999[19], f"{method}: chi2={stat:.1f}"


def test_auto_works_without_key():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (64, 200)), jnp.float32)
    u = jnp.asarray(rng.uniform(0, 1, (64,)), jnp.float32)
    idx = np.asarray(sample_categorical(w, u=u, method="auto"))
    assert idx.shape == (64,) and (0 <= idx).all() and (idx < 200).all()


def test_auto_1d_logits():
    """Regression: 1-D logits must lift to (1, K) before auto resolution."""
    idx = sample_from_logits(jnp.array([0.0, 5.0, 1.0]), jax.random.PRNGKey(0))
    assert idx.shape == () and 0 <= int(idx) < 3
    greedy = sample_from_logits(
        jnp.array([0.0, 5.0, 1.0]), jax.random.PRNGKey(0), temperature=0.0
    )
    assert int(greedy) == 1


def test_auto_inside_jit():
    w = jnp.ones((128, 512), jnp.float32)
    f = jax.jit(lambda w, k: sample_categorical(w, key=k, method="auto"))
    idx = np.asarray(f(w, jax.random.PRNGKey(0)))
    assert idx.shape == (128,) and (idx < 512).all()


def test_plan_from_sampler_spec(weights, uniforms):
    from repro.configs.base import SamplerSpec

    p = sampling.plan(SamplerSpec(method="fenwick", W=W), shape=(B, K))
    assert (p.method, p.W, p.shape) == ("fenwick", W, (B, K))
    exp = legacy_draw("fenwick", weights, uniforms, None)
    np.testing.assert_array_equal(
        np.asarray(p.sample(weights, u=uniforms)), np.asarray(exp)
    )


@pytest.mark.parametrize("method", ["fenwick", "two_level", "gumbel", "alias"])
def test_multi_draw(method, weights):
    """num_samples > 1 returns (S, B) draws, all randomness device-side,
    statistically matching the target distribution."""
    p = sampling.plan(weights.shape, method=method, W=W)
    dist = p.build(weights)
    S = 4000
    out = np.asarray(p.draw(dist, key=jax.random.PRNGKey(2), num_samples=S))
    assert out.shape == (S, B)
    probs = np.asarray(weights[0] / weights[0].sum())
    counts = np.bincount(out[:, 0], minlength=K).astype(np.float64)
    stat, _ = _chi2_stat(counts, probs)
    assert stat < CHI2_999[39], f"{method}: chi2={stat:.1f}"
    # distinct draws across samples (not S copies of one draw)
    assert len({tuple(r) for r in out[:50]}) > 1


def test_multi_draw_with_explicit_uniform_matrix(weights):
    p = sampling.plan(weights.shape, method="fenwick", W=W)
    dist = p.build(weights)
    rng = np.random.default_rng(3)
    us = jnp.asarray(rng.uniform(0, 1, (3, B)), jnp.float32)
    out = p.draw(dist, u=us)
    assert out.shape == (3, B)
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(out[i]), np.asarray(p.draw(dist, u=us[i]))
        )


# ---------------------------------------------------------------------------
# Shim equivalence: byte-identical to the pre-redesign implementation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", U_METHODS)
def test_shim_byte_identical_u_methods(method, weights, uniforms):
    """sample_categorical(w, u=u, method=m, W=W) must reproduce the
    pre-redesign draws bit-for-bit."""
    got = sample_categorical(weights, u=uniforms, method=method, W=W)
    exp = legacy_draw(method, weights, uniforms, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("method", ALL_METHODS)
def test_shim_byte_identical_key_methods(method, weights):
    """Key-driven calls: same key => same uniforms/noise => same draws."""
    key = jax.random.PRNGKey(9)
    got = sample_categorical(weights, key=key, method=method, W=W)
    if method in ("gumbel", "alias"):
        exp = legacy_draw(method, weights, None, key)
    else:
        u = jax.random.uniform(key, (B,), dtype=jnp.float32)
        exp = legacy_draw(method, weights, u, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_shim_logits_byte_identical(weights):
    """sample_from_logits must reproduce the pre-redesign pipeline
    (stable softmax -> key-derived uniform -> draw) bit-for-bit."""
    rng = np.random.default_rng(17)
    logits = jnp.asarray(rng.normal(size=(B, K)), jnp.float32)
    key = jax.random.PRNGKey(21)
    t = 0.7
    for method in ("fenwick", "two_level", "prefix"):
        got = sample_from_logits(logits, key, temperature=t, method=method, W=W)
        z = logits / t
        z = z - jnp.max(z, axis=-1, keepdims=True)
        u = jax.random.uniform(key, (B,), dtype=jnp.float32)
        exp = legacy_draw(method, jnp.exp(z), u, None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    # gumbel samples in logit space (no exp/log round trip), as before
    got = sample_from_logits(logits, key, temperature=t, method="gumbel")
    exp = _gumbel.draw_gumbel_logits(logits / t, key)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("method", ALL_METHODS)
def test_shim_statistically_matches_new_api(method):
    """Chi-squared gate: old shim and new API draw the same distribution."""
    Kd, N = 20, 60_000
    rng = np.random.default_rng(5)
    probs = rng.dirichlet(np.full(Kd, 0.3))
    w = jnp.tile(jnp.array(probs, jnp.float32)[None], (N, 1))
    for draw_fn in (
        lambda: sample_categorical(w, key=jax.random.PRNGKey(1), method=method, W=8),
        lambda: sampling.plan(w.shape, method=method, W=8).sample(
            w, key=jax.random.PRNGKey(1)
        ),
    ):
        idx = np.asarray(draw_fn())
        counts = np.bincount(idx, minlength=Kd).astype(np.float64)
        stat, _ = _chi2_stat(counts, probs)
        assert stat < CHI2_999[19], f"{method}: chi2={stat:.1f}"


# ---------------------------------------------------------------------------
# Table cache: content digest kills the stale-table footgun
# ---------------------------------------------------------------------------


def test_dist_key_no_stale_table_on_weight_change(uniforms):
    """Pre-redesign footgun: same dist_key + silently changed weights
    served the stale table.  The content digest must rebuild instead."""
    tables.reset_table_cache()
    wa = jnp.concatenate(
        [jnp.full((B, K // 2), 10.0), jnp.full((B, K // 2), 0.01)], axis=1
    )
    wb = jnp.concatenate(  # same shape/dtype/total, mass moved to the right
        [jnp.full((B, K // 2), 0.01), jnp.full((B, K // 2), 10.0)], axis=1
    )
    a = sample_categorical(wa, u=uniforms, method="fenwick", W=W, dist_key="d")
    b = sample_categorical(wb, u=uniforms, method="fenwick", W=W, dist_key="d")
    exp_b = sample_categorical(wb, u=uniforms, method="fenwick", W=W)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(exp_b))
    assert np.asarray(a).mean() < K / 2 < np.asarray(b).mean()


def test_dist_key_same_weights_still_hit(weights, uniforms):
    tables.reset_table_cache()
    cache = tables.get_table_cache()
    a = sample_categorical(weights, u=uniforms, method="fenwick", W=W, dist_key="p")
    b = sample_categorical(weights, u=uniforms, method="fenwick", W=W, dist_key="p")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert cache.hits >= 1


def test_content_digest_distinguishes_permutations(weights):
    d1 = tables.content_digest(weights)
    d2 = tables.content_digest(weights[:, ::-1])
    d3 = tables.content_digest(weights)
    assert d1 == d3 and d1 != d2
    assert tables.content_digest(weights.astype(jnp.bfloat16)) != d1
    # tracers have no content: no digest, no caching
    jax.jit(lambda w: (_ for _ in ()).throw(SystemExit)
            if tables.content_digest(w) is not None else w)(weights)


def test_table_cache_hits_and_invalidation():
    cache = tables.TableCache(max_entries=4)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (8, 64)), jnp.float32)
    t1 = cache.get_or_build("phi", "fenwick", w, W=8)
    t2 = cache.get_or_build("phi", "fenwick", w, W=8)
    assert t1 is t2 and cache.stats() == {"entries": 1, "hits": 1, "misses": 1}
    assert cache.invalidate("phi") == 1 and len(cache) == 0
    # inside jit (tracers) the cache must pass through, not capture tracers
    jax.jit(lambda w: cache.get_or_build("phi", "fenwick", w, W=8))(w)
    assert len(cache) == 0


def test_dist_key_integer_weights_match_uncached():
    """Regression: the cached-table path must normalize dtype like the
    uncached one (an integer table truncates the uniforms to 0)."""
    w = jnp.full((4, 8), 1, jnp.int32)
    u = jnp.full((4,), 0.9, jnp.float32)
    tables.reset_table_cache()
    a = np.asarray(sample_categorical(w, u=u, method="fenwick", W=8))
    b = np.asarray(
        sample_categorical(w, u=u, method="fenwick", W=8, dist_key="int")
    )
    np.testing.assert_array_equal(a, b)
    assert (b == 7).all()


def test_draws_hint_ignored_without_dist_key():
    """No dist_key => no cross-call reuse => auto must not select a method
    on the strength of a reuse that never happens: the call plans at
    draws=1."""
    sampling.reset_plans()
    w = jnp.ones((64, 4096), jnp.float32)
    sample_categorical(w, key=jax.random.PRNGKey(0), method="auto", draws=512)
    assert sampling.plan_stats()["plan_misses"] == 1
    p = sampling.plan(w.shape, method="auto", draws=1, has_key=True)
    assert sampling.plan_stats()["plan_hits"] == 1
    assert p.draws == 1 and p.method != "fenwick"


def test_dist_key_draws_match_uncached():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (32, 48)), jnp.float32)
    u = jnp.asarray(rng.uniform(0, 1, (32,)), jnp.float32)
    tables.reset_table_cache()
    a = sample_categorical(w, u=u, method="fenwick", W=8)
    b = sample_categorical(w, u=u, method="fenwick", W=8, dist_key="w")
    c = sample_categorical(w, u=u, method="fenwick", W=8, dist_key="w")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert tables.get_table_cache().hits >= 1


# ---------------------------------------------------------------------------
# bfloat16 logits path
# ---------------------------------------------------------------------------


def test_bf16_logits_not_upcast():
    w = sampling.logits_to_weights(
        jnp.zeros((4, 32), jnp.bfloat16), temperature=0.8
    )
    assert w.dtype == jnp.bfloat16
    assert sampling.logits_to_weights(jnp.zeros((4, 32), jnp.float32)).dtype == (
        jnp.float32
    )


def test_bf16_logits_sample_and_real_dtype_seen():
    sampling.reset_plans()
    rng = np.random.default_rng(23)
    logits = jnp.asarray(rng.normal(size=(32, 256)), jnp.bfloat16)
    idx = sample_from_logits(logits, jax.random.PRNGKey(0), temperature=0.9)
    assert idx.shape == (32,) and (np.asarray(idx) < 256).all()
    # the plan must record the REAL dtype, not float32: re-planning the
    # bfloat16 workload is a memo hit
    p = sampling.plan(logits.shape, method="auto", dtype="bfloat16")
    assert sampling.plan_stats()["plan_hits"] == 1
    assert p.dtype == "bfloat16"
    # low temperature still concentrates on the argmax row-wise
    lb = jnp.tile(logits[:1], (2000, 1))
    top = np.asarray(
        sample_from_logits(lb, jax.random.PRNGKey(1), temperature=0.05,
                           method="fenwick", W=16)
    )
    assert (top == int(np.argmax(np.asarray(logits, np.float32)[0]))).mean() > 0.95


# ---------------------------------------------------------------------------
# Kernel table-in/table-out entry points
# ---------------------------------------------------------------------------


def test_kernel_table_in_table_out(weights, uniforms):
    from repro.kernels.butterfly_sample import (
        build_block_sums,
        butterfly_sample,
        butterfly_sample_from_sums,
    )

    wp, running = build_block_sums(weights, W=W)
    assert running.shape[1] == wp.shape[1] // W
    got = butterfly_sample_from_sums(wp, running, uniforms, K=K, W=W)
    exp = butterfly_sample(weights, uniforms, W=W)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------


def test_sampler_spec_resolution():
    from repro.configs.base import ModelConfig, SamplerSpec

    base = dict(
        name="t", family="dense", num_layers=1, d_model=8, num_heads=2,
        num_kv_heads=1, d_ff=16, vocab_size=64,
    )
    legacy = ModelConfig(**base, sampler_method="fenwick", sampler_W=8)
    assert legacy.sampler_spec == SamplerSpec(method="fenwick", W=8)
    structured = ModelConfig(
        **base, sampler=SamplerSpec(method="two_level", W=16, draws=4)
    )
    assert structured.sampler_spec.method == "two_level"
    assert structured.sampler_spec.draws == 4
    # the structured field wins over the legacy pair
    both = ModelConfig(
        **base, sampler=SamplerSpec(method="prefix"), sampler_method="gumbel"
    )
    assert both.sampler_spec.method == "prefix"
