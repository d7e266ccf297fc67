"""Tiled-grid kernel rewrite: equivalence vs the searchsorted oracle across
W and padding edges, the factored (zero-materialization) path end to end,
multi-draw determinism, interpret-default routing, and the tile
parameters a plan records."""

import numpy as np
import jax
import jax.extend
import jax.numpy as jnp
import pytest

from repro import sampling
from repro.kernels import runtime
from repro.kernels.butterfly_sample.kernel import (
    blocksums_pallas,
    build_block_sums_pallas,
    butterfly_sample_pallas,
    sample_from_block_sums_pallas,
)
from repro.kernels.butterfly_sample.ref import butterfly_sample_ref
from repro.kernels.lda_draw import (
    lda_build_running,
    lda_draw_factored,
    lda_draw_from_running,
)
from repro.kernels.lda_draw.ref import lda_draw_ref

from test_sampler_stats import CHI2_999, _chi2_stat

WS = [8, 16, 32, 64]


# ---------------------------------------------------------------------------
# Tiled fused draw + tiled table-in pass B vs the oracle
# ---------------------------------------------------------------------------


class TestTiledButterflySample:
    @pytest.mark.parametrize("W", WS)
    @pytest.mark.parametrize("B,K,tb", [(8, 64, 4), (24, 300, 8), (64, 1024, 16)])
    def test_w_sweep(self, W, B, K, tb):
        rng = np.random.default_rng(B * 37 + K + W)
        w = rng.integers(1, 1000, size=(B, K)).astype(np.float32)
        u = rng.uniform(0, 1, size=(B,)).astype(np.float32)
        got = np.array(
            butterfly_sample_pallas(jnp.array(w), jnp.array(u), W=W, tb=tb)
        )
        ref = np.array(butterfly_sample_ref(jnp.array(w), jnp.array(u)))
        np.testing.assert_array_equal(got, ref)

    @pytest.mark.parametrize(
        "B,K,tb", [(5, 17, 8), (1, 2, 8), (3, 2000, 8), (7, 129, 4), (13, 31, 16)]
    )
    def test_nonmultiple_padding_edges(self, B, K, tb):
        """B not a multiple of tb, K not a multiple of W or tk."""
        W = 8
        rng = np.random.default_rng(B * 101 + K)
        w = rng.integers(1, 500, size=(B, K)).astype(np.float32)
        u = rng.uniform(0, 1, size=(B,)).astype(np.float32)
        ref = np.array(butterfly_sample_ref(jnp.array(w), jnp.array(u)))
        got = np.array(
            butterfly_sample_pallas(jnp.array(w), jnp.array(u), W=W, tb=tb)
        )
        np.testing.assert_array_equal(got, ref)
        wp, running = build_block_sums_pallas(jnp.array(w), W=W, tb=tb)
        got2 = np.array(
            sample_from_block_sums_pallas(
                wp, running, jnp.array(u), B=B, K=K, W=W, tb=tb
            )
        )
        np.testing.assert_array_equal(got2, ref)

    def test_vmem_guard_falls_back_to_two_pass(self, monkeypatch):
        """When even a tb=8 row tile would exceed the fused-draw VMEM
        budget, butterfly_sample_pallas must transparently take the
        two-pass route and stay oracle-exact."""
        from repro.kernels.butterfly_sample import kernel as bk
        from repro.kernels.lda_draw import kernel as lk

        monkeypatch.setattr(bk, "_FUSED_TILE_BYTES", 1024)
        rng = np.random.default_rng(99)
        B, K, W = 6, 257, 8          # distinct shape: forces a fresh trace
        w = jnp.array(rng.integers(1, 200, (B, K)).astype(np.float32))
        u = jnp.array(rng.uniform(0, 1, (B,)).astype(np.float32))
        got = np.array(butterfly_sample_pallas(w, u, W=W, tb=16))
        np.testing.assert_array_equal(
            got, np.array(butterfly_sample_ref(w, u))
        )
        C, N, V = 2, 3, 9
        theta = jnp.array(rng.integers(1, 50, (C, K)).astype(np.float32))
        phi = jnp.array(rng.integers(1, 50, (V, K)).astype(np.float32))
        words = jnp.array(rng.integers(0, V, (C * N,)), jnp.int32)
        doc_ids = jnp.arange(C * N, dtype=jnp.int32) // N
        uu = jnp.array(rng.uniform(0, 1, (C * N,)).astype(np.float32))
        got2 = np.array(
            lk.lda_draw_docs_pallas(theta, phi, doc_ids, words, uu, W=W, tb=16)
        )
        np.testing.assert_array_equal(
            got2, np.array(lda_draw_ref(theta[doc_ids], phi, words, uu))
        )

    @pytest.mark.parametrize("W", WS)
    def test_table_in_matches_fused(self, W):
        B, K, tb = 12, 200, 8
        rng = np.random.default_rng(W)
        w = jnp.array(rng.integers(1, 100, size=(B, K)).astype(np.float32))
        u = jnp.array(rng.uniform(0, 1, size=(B,)).astype(np.float32))
        fused = np.array(butterfly_sample_pallas(w, u, W=W, tb=tb))
        wp, running = build_block_sums_pallas(w, W=W, tb=tb)
        tablein = np.array(
            sample_from_block_sums_pallas(wp, running, u, B=B, K=K, W=W, tb=tb)
        )
        np.testing.assert_array_equal(fused, tablein)


class TestTiledFactoredDraw:
    @pytest.mark.parametrize("W", WS)
    @pytest.mark.parametrize("impl", ["pallas", "xla"])
    def test_w_sweep_vs_oracle(self, W, impl):
        C, N, V, K = 5, 14, 33, 200
        B = C * N
        rng = np.random.default_rng(W + (0 if impl == "pallas" else 1))
        theta = jnp.array(rng.integers(1, 100, size=(C, K)).astype(np.float32))
        phi = jnp.array(rng.integers(1, 100, size=(V, K)).astype(np.float32))
        words = jnp.array(rng.integers(0, V, size=(B,)), jnp.int32)
        doc_ids = jnp.arange(B, dtype=jnp.int32) // N
        u = jnp.array(rng.uniform(0, 1, size=(B,)).astype(np.float32))
        got = np.array(
            lda_draw_factored(theta, phi, doc_ids, words, u, W=W, impl=impl)
        )
        ref = np.array(lda_draw_ref(theta[doc_ids], phi, words, u))
        np.testing.assert_array_equal(got, ref)

    @pytest.mark.parametrize("impl", ["pallas", "xla"])
    def test_table_in_and_multidraw(self, impl):
        C, N, V, K, W, S = 4, 9, 21, 50, 8, 3
        B = C * N
        rng = np.random.default_rng(7)
        theta = jnp.array(rng.integers(1, 64, size=(C, K)).astype(np.float32))
        phi = jnp.array(rng.integers(1, 64, size=(V, K)).astype(np.float32))
        words = jnp.array(rng.integers(0, V, size=(B,)), jnp.int32)
        doc_ids = jnp.arange(B, dtype=jnp.int32) // N
        tp, pp, running = lda_build_running(
            theta, phi, doc_ids, words, W=W, impl=impl
        )
        us = jnp.array(rng.uniform(0, 1, size=(S, B)).astype(np.float32))
        got = np.array(
            lda_draw_from_running(
                tp, pp, running, us, doc_ids, words, K=K, W=W, impl=impl
            )
        )
        ref = np.stack(
            [
                np.array(lda_draw_ref(theta[doc_ids], phi, words, us[s]))
                for s in range(S)
            ]
        )
        np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# Factored Categorical: build / refresh / statistics
# ---------------------------------------------------------------------------


class TestFactoredCategorical:
    def _factors(self, seed, C=3, N=16, V=25, K=20):
        rng = np.random.default_rng(seed)
        theta = jnp.array(rng.uniform(0.5, 1.5, (C, K)).astype(np.float32))
        phi = jnp.array(rng.uniform(0.5, 1.5, (V, K)).astype(np.float32))
        words = jnp.array(rng.integers(0, V, C * N), jnp.int32)
        doc_ids = jnp.arange(C * N, dtype=jnp.int32) // N
        return theta, phi, words, doc_ids

    def test_from_factors_matches_materialized(self):
        theta, phi, words, doc_ids = self._factors(0)
        dist = sampling.Categorical.from_factors(theta, phi, words, doc_ids, W=8)
        assert dist.method == "lda_kernel"
        rng = np.random.default_rng(1)
        u = jnp.array(rng.uniform(0, 1, dist.shape[0]).astype(np.float32))
        got = np.array(dist.draw(u=u))
        ref = np.array(lda_draw_ref(theta[doc_ids], phi, words, u))
        np.testing.assert_array_equal(got, ref)

    def test_refresh_from_factors_chi2(self):
        """Statistical gate on the fused factored-refresh path: refresh
        with new factors, multi-draw, chi-square the first sample's
        marginal against its true distribution."""
        theta0, phi0, words, doc_ids = self._factors(2)
        dist = sampling.Categorical.from_factors(theta0, phi0, words, doc_ids, W=8)
        theta1, phi1, _, _ = self._factors(3)
        dist = dist.refresh_from_factors(theta1, phi1)
        S = 4000
        out = np.array(dist.draw(key=jax.random.PRNGKey(0), num_samples=S))
        assert out.shape == (S, dist.shape[0])
        w0 = np.array(theta1)[int(doc_ids[0])] * np.array(phi1)[int(words[0])]
        probs = w0 / w0.sum()
        counts = np.bincount(out[:, 0], minlength=len(probs)).astype(np.float64)
        stat, _ = _chi2_stat(counts, probs)
        assert stat < CHI2_999[19], f"chi2={stat:.1f}"

    def test_refresh_direction_errors(self):
        theta, phi, words, doc_ids = self._factors(4)
        dist = sampling.Categorical.from_factors(theta, phi, words, doc_ids, W=8)
        with pytest.raises(ValueError, match="refresh_from_factors"):
            dist.refreshed(jnp.ones(dist.shape, jnp.float32))
        flat = sampling.Categorical.from_weights(
            jnp.ones((4, 16), jnp.float32), method="two_level", W=8
        )
        with pytest.raises(ValueError, match="refreshed"):
            flat.refresh_from_factors(theta, phi)

    def test_pytree_roundtrip_preserves_tb(self):
        theta, phi, words, doc_ids = self._factors(5)
        dist = sampling.Categorical.from_factors(
            theta, phi, words, doc_ids, W=8, tb=16
        )
        leaves, treedef = jax.tree_util.tree_flatten(dist)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.method == "lda_kernel" and back.tb == 16
        u = jnp.full((dist.shape[0],), 0.25, jnp.float32)
        np.testing.assert_array_equal(
            np.array(dist.draw(u=u)), np.array(back.draw(u=u))
        )

    def test_plan_build_from_factors_nonfactored_method(self):
        """A flat-method plan materializes through the same entry point."""
        theta, phi, words, doc_ids = self._factors(6)
        B, K = int(words.shape[0]), int(theta.shape[1])
        p = sampling.plan((B, K), method="two_level", W=8, factored=True)
        dist = p.build_from_factors(theta, phi, words, doc_ids)
        assert dist.method == "two_level"
        u = jnp.full((B,), 0.7, jnp.float32)
        flat = theta[doc_ids] * phi[words]
        exp = sampling.Categorical.from_weights(flat, method="two_level", W=8)
        np.testing.assert_array_equal(
            np.array(dist.draw(u=u)), np.array(exp.draw(u=u))
        )


# ---------------------------------------------------------------------------
# Multi-draw: determinism + tiled pass-B equivalence
# ---------------------------------------------------------------------------


class TestMultiDraw:
    @pytest.mark.parametrize("method", ["kernel", "two_level"])
    def test_fixed_key_determinism(self, method):
        rng = np.random.default_rng(8)
        w = jnp.array(rng.uniform(0.1, 1.0, (16, 96)).astype(np.float32))
        p = sampling.plan(w.shape, method=method, W=8)
        dist = p.build(w)
        key = jax.random.PRNGKey(12)
        a = np.array(p.draw(dist, key=key, num_samples=5))
        b = np.array(p.draw(dist, key=key, num_samples=5))
        np.testing.assert_array_equal(a, b)
        assert a.shape == (5, 16)
        # distinct draws across samples (not 5 copies of one draw)
        assert len({tuple(r) for r in a}) > 1

    def test_kernel_multidraw_matches_single_draws(self):
        """The one-launch tiled pass B (rows indirection) must agree with
        S independent single-u draws."""
        rng = np.random.default_rng(9)
        B, K, W, S = 10, 130, 8, 4
        w = jnp.array(rng.uniform(0.1, 1.0, (B, K)).astype(np.float32))
        p = sampling.plan((B, K), method="kernel", W=W)
        dist = p.build(w)
        us = jnp.array(rng.uniform(0, 1, (S, B)).astype(np.float32))
        batched = np.array(p.draw(dist, u=us))
        singles = np.stack([np.array(p.draw(dist, u=us[s])) for s in range(S)])
        np.testing.assert_array_equal(batched, singles)

    def test_lda_kernel_multidraw_determinism(self):
        rng = np.random.default_rng(10)
        C, N, V, K = 3, 8, 15, 24
        theta = jnp.array(rng.uniform(0.5, 1.5, (C, K)).astype(np.float32))
        phi = jnp.array(rng.uniform(0.5, 1.5, (V, K)).astype(np.float32))
        words = jnp.array(rng.integers(0, V, C * N), jnp.int32)
        dist = sampling.Categorical.from_factors(
            theta, phi, words, jnp.arange(C * N, dtype=jnp.int32) // N, W=8
        )
        key = jax.random.PRNGKey(3)
        a = np.array(dist.draw(key=key, num_samples=4))
        b = np.array(dist.draw(key=key, num_samples=4))
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Zero-materialization: the fused Gibbs z-draw holds no (C*N, K) buffer
# ---------------------------------------------------------------------------


def _all_avals(jaxpr):
    """Every intermediate/output aval in a jaxpr, recursively."""
    seen = []

    def walk(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                if hasattr(v, "aval"):
                    seen.append(v.aval)
            for p in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                    p, is_leaf=lambda x: isinstance(x, jax.extend.core.ClosedJaxpr)
                ):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        walk(sub)
        return seen

    return walk(jaxpr)


class TestZeroMaterialization:
    def test_scan_draw_has_no_flat_weight_intermediate(self):
        """The acceptance gate: the fused factored Gibbs z-draw never
        allocates a (C*N, K)-sized weight buffer anywhere in its jaxpr —
        including the (C, N, K) unflattened form and the repeated-theta
        form the old chunk loop used."""
        from repro.lda import gibbs

        chunk, maxN, K, V, M = 16, 12, 64, 50, 32
        B = chunk * maxN                              # samples per chunk
        rng = np.random.default_rng(11)
        theta = jnp.array(rng.uniform(0.1, 1.0, (M, K)).astype(np.float32))
        phi = jnp.array(rng.uniform(0.1, 1.0, (V, K)).astype(np.float32))
        docs = jnp.array(rng.integers(0, V, (M, maxN)), jnp.int32)
        key = jax.random.PRNGKey(0)

        jaxpr = jax.make_jaxpr(
            lambda t, p, d, k: gibbs._scan_draw(
                t, p, d, k, method="lda_kernel", W=8, chunk=chunk
            )
        )(theta, phi, docs, key)
        flat_elems = B * K
        offending = [
            a for a in _all_avals(jaxpr.jaxpr)
            if hasattr(a, "shape") and a.ndim >= 2
            and int(np.prod(a.shape)) >= flat_elems
            and a.shape[-1] in (K, K * maxN)
        ]
        assert not offending, (
            f"fused z-draw materializes weight-sized buffers: "
            f"{[a.shape for a in offending]}"
        )

    def test_scan_draw_matches_legacy_loop(self):
        """The jitted lax.scan path and the legacy per-chunk Python loop
        draw identical z (same key schedule, same compiled draws)."""
        from repro.lda import gibbs, synthesize_corpus
        from repro.lda.gibbs import draw_z, init_state

        corpus = synthesize_corpus(seed=5, M=32, V=40, K=6, avg_len=12, max_len=20)
        state = init_state(jax.random.PRNGKey(1), corpus, 6)
        docs = jnp.asarray(corpus.docs)
        z_scan = np.array(
            draw_z(state, docs, method="fenwick", W=8, chunk=16, dists=None)
        )
        z_loop = np.array(
            draw_z(state, docs, method="fenwick", W=8, chunk=16, dists={})
        )
        np.testing.assert_array_equal(z_scan, z_loop)

    def test_gibbs_factored_dists_cache_refreshes(self):
        """The legacy dists= path holds factored Categoricals and
        refreshes them (refresh_from_factors) across sweeps."""
        from repro.lda import gibbs_step, init_state, perplexity, synthesize_corpus

        corpus = synthesize_corpus(seed=6, M=24, V=40, K=5, avg_len=10, max_len=16)
        state = init_state(jax.random.PRNGKey(2), corpus, 5)
        p0 = perplexity(state, corpus)
        dists = {}
        for _ in range(4):
            state = gibbs_step(
                state, corpus, method="lda_kernel", W=8, dists=dists
            )
        assert dists and all(
            d.method == "lda_kernel" for d in dists.values()
        )
        p1 = perplexity(state, corpus)
        assert np.isfinite(p1) and p1 < p0


# ---------------------------------------------------------------------------
# Interpret-mode defaults route through the shared backend helper
# ---------------------------------------------------------------------------


class TestInterpretDefaults:
    def test_policy(self):
        assert runtime.default_interpret("tpu") is False
        assert runtime.default_interpret("cpu") is True
        assert runtime.default_interpret("gpu") is True
        assert runtime.resolve_interpret(None) == runtime.default_interpret()
        assert runtime.resolve_interpret(True) is True
        assert runtime.resolve_interpret(False) is False

    def test_low_level_entry_points_accept_none(self):
        """The *_pallas entry points no longer hard-default interpret=True:
        they resolve via the helper (True here, on CPU) and still run."""
        rng = np.random.default_rng(12)
        w = jnp.array(rng.integers(1, 50, (8, 32)).astype(np.float32))
        bs = np.array(blocksums_pallas(w, W=8, tb=4, tk=32, interpret=None))
        np.testing.assert_allclose(
            bs, np.array(w).reshape(8, 4, 8).sum(-1), rtol=1e-6
        )
        u = jnp.array(rng.uniform(0, 1, (8,)).astype(np.float32))
        got = np.array(butterfly_sample_pallas(w, u, W=8, tb=4, interpret=None))
        np.testing.assert_array_equal(
            got, np.array(butterfly_sample_ref(w, u))
        )

    def test_butterfly_table_entry_point(self):
        from repro.kernels.butterfly_table import butterfly_table
        from repro.kernels.butterfly_table.ref import butterfly_table_ref

        rng = np.random.default_rng(13)
        w = jnp.array(rng.integers(1, 50, (8, 24)).astype(np.float32))
        got = np.array(butterfly_table(w, W=8, interpret=None))
        np.testing.assert_allclose(
            got, np.array(butterfly_table_ref(w, W=8)), rtol=1e-6
        )


# ---------------------------------------------------------------------------
# The plan carries the kernel's launch tiles
# ---------------------------------------------------------------------------


class TestTileParamsInCache:
    def test_resolve_full_records_tiles(self):
        """An auto plan records the complete launch config: W and the
        (tb, tk) tiles the tiled kernels take, from kernels.runtime."""
        from repro.kernels import runtime

        p = sampling.plan((256, 1024), method="auto")
        assert (p.W, p.tb, p.tk) == (
            runtime.default_w(1024), runtime.default_tb(256),
            runtime.default_tk(1024, runtime.default_w(1024)),
        )
        assert (p.W, p.tb, p.tk) == (32, 8, 512)
        # re-planning the workload restores the same launch config
        again = sampling.plan((256, 1024), method="auto")
        assert (again.method, again.W, again.tb, again.tk) == (
            p.method, p.W, p.tb, p.tk
        )

    def test_plan_carries_tiles(self):
        p = sampling.plan((64, 256), method="two_level", W=8)
        assert p.tb > 0 and p.tk > 0
