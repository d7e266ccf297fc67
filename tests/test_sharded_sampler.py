"""Mesh-sharded sampler: counter-RNG determinism, in-kernel RNG vs the
XLA twin, topology-aware plan memoization, the v3 tuning-cache schema,
mesh helpers — and (in an 8-virtual-device subprocess, so XLA_FLAGS can't
leak into this process) device-count invariance of sharded draws plus the
jaxpr collective gates: ZERO collectives on the draw path, exactly one
psum (the AD-LDA counts all-reduce) in the distributed Gibbs sweep."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import sampling
from repro.kernels import rng
from repro.kernels.butterfly_sample import ops as kops
from repro.sampling import sharded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


# ---------------------------------------------------------------------------
# Counter RNG: the threefry twin
# ---------------------------------------------------------------------------


class TestCounterRNG:
    def test_deterministic_and_in_range(self):
        seed = rng.seed_from_key(jax.random.PRNGKey(3))
        u1 = np.array(rng.row_uniforms(seed, 0, 4096))
        u2 = np.array(rng.row_uniforms(seed, 0, 4096))
        np.testing.assert_array_equal(u1, u2)
        assert (u1 >= 0).all() and (u1 < 1).all()
        # statistically uniform-ish (loose: mean within 3 sigma)
        assert abs(u1.mean() - 0.5) < 3 * (1 / np.sqrt(12 * 4096))
        assert len(np.unique(u1)) > 4000

    def test_rows_are_global_counters(self):
        """u of row r never depends on where the (row-offset) window
        starts — the property device-count invariance rests on."""
        seed = rng.seed_from_key(jax.random.PRNGKey(0))
        full = np.array(rng.row_uniforms(seed, 0, 64))
        part = np.array(rng.row_uniforms(seed, 48, 16))
        np.testing.assert_array_equal(part, full[48:])

    def test_draw_index_is_second_counter(self):
        seed = rng.seed_from_key(jax.random.PRNGKey(1))
        multi = np.array(rng.multi_row_uniforms(seed, 0, 32, 4))
        np.testing.assert_array_equal(
            multi[0], np.array(rng.row_uniforms(seed, 0, 32))
        )
        np.testing.assert_array_equal(
            multi[2], np.array(rng.row_uniforms(seed, 0, 32, draw=2))
        )
        assert (multi[0] != multi[1]).any()

    def test_fold_separates_streams(self):
        seed = rng.seed_from_key(jax.random.PRNGKey(2))
        a = np.array(rng.uniform(rng.fold(seed, rng.TAG_U, 0), np.arange(64)))
        b = np.array(
            rng.uniform(rng.fold(seed, rng.TAG_GUMBEL, 0), np.arange(64))
        )
        assert (a != b).all()

    def test_seed_from_key_accepts_raw_and_typed(self):
        raw = jax.random.PRNGKey(9)
        s1 = np.array(rng.seed_from_key(raw))
        typed = jax.random.key(9)
        s2 = np.array(rng.seed_from_key(typed))
        np.testing.assert_array_equal(s1, s2)
        assert s1.dtype == np.uint32 and s1.shape == (2,)


# ---------------------------------------------------------------------------
# In-kernel RNG == XLA twin, across routes and shards
# ---------------------------------------------------------------------------


class TestInKernelRNG:
    def _w(self, B=13, K=100):
        r = np.random.default_rng(B * 7 + K)
        return jnp.array(r.uniform(0.1, 1.0, (B, K)).astype(np.float32))

    def test_fused_rng_matches_counter_oracle(self):
        from repro.kernels.butterfly_sample.ref import butterfly_sample_ref

        B, K, W = 13, 100, 8
        w = self._w(B, K)
        seed = rng.seed_from_key(jax.random.PRNGKey(42))
        got = np.array(kops.butterfly_sample_rng(w, seed, W=W))
        u = rng.row_uniforms(rng.fold(seed, rng.TAG_U, 0), 0, B)
        ref = np.array(butterfly_sample_ref(w, u))
        np.testing.assert_array_equal(got, ref)

    def test_two_pass_fallback_is_bit_identical(self, monkeypatch):
        """The VMEM-overflow route derives the same counters XLA-side."""
        from repro.kernels.butterfly_sample import kernel as bk

        B, K, W = 11, 310, 8
        w = self._w(B, K)
        seed = rng.seed_from_key(jax.random.PRNGKey(5))
        fused = np.array(kops.butterfly_sample_rng(w, seed, W=W))
        monkeypatch.setattr(bk, "_FUSED_TILE_BYTES", 256)
        two_pass = np.array(kops.butterfly_sample_rng(w, seed, W=W, tb=16))
        np.testing.assert_array_equal(fused, two_pass)

    def test_pass_b_rng_and_multidraw(self):
        B, K, W, S = 13, 100, 8, 3
        w = self._w(B, K)
        seed = rng.seed_from_key(jax.random.PRNGKey(42))
        single = np.array(kops.butterfly_sample_rng(w, seed, W=W))
        wp, running = kops.build_block_sums(w, W=W)
        tablein = np.array(
            kops.butterfly_sample_from_sums_rng(wp, running, seed, B=B, K=K, W=W)
        )
        np.testing.assert_array_equal(single, tablein)
        multi = np.array(
            kops.butterfly_sample_from_sums_rng(
                wp, running, seed, B=B, K=K, S=S, W=W
            )
        )
        assert multi.shape == (S, B)
        # draw 0 is the S=1 draw: launch count grew, counters didn't move
        np.testing.assert_array_equal(multi[0], single)

    def test_row_offset_is_shard_equivalence(self):
        B, K, W = 12, 64, 8
        w = self._w(B, K)
        seed = rng.seed_from_key(jax.random.PRNGKey(8))
        full = np.array(kops.butterfly_sample_rng(w, seed, W=W))
        lo = np.array(kops.butterfly_sample_rng(w[:6], seed, row_offset=0, W=W))
        hi = np.array(kops.butterfly_sample_rng(w[6:], seed, row_offset=6, W=W))
        np.testing.assert_array_equal(np.concatenate([lo, hi]), full)

    def test_lda_factored_rng_matches_counter_u(self):
        from repro.kernels.lda_draw import lda_draw_factored, lda_draw_factored_rng

        C, N, V, K = 4, 8, 15, 48
        B = C * N
        r = np.random.default_rng(3)
        theta = jnp.array(r.uniform(0.5, 1.5, (C, K)).astype(np.float32))
        phi = jnp.array(r.uniform(0.5, 1.5, (V, K)).astype(np.float32))
        words = jnp.array(r.integers(0, V, B), jnp.int32)
        doc_ids = jnp.arange(B, dtype=jnp.int32) // N
        seed = rng.seed_from_key(jax.random.PRNGKey(4))
        got = np.array(
            lda_draw_factored_rng(theta, phi, doc_ids, words, seed, W=8)
        )
        u = rng.row_uniforms(rng.fold(seed, rng.TAG_U, 0), 0, B)
        exp = np.array(lda_draw_factored(theta, phi, doc_ids, words, u, W=8))
        np.testing.assert_array_equal(got, exp)


# ---------------------------------------------------------------------------
# Sharded plans on a 1-device mesh (semantics; scaling runs in subprocess)
# ---------------------------------------------------------------------------


class TestShardedPlan:
    def test_plan_memo_distinguishes_topology(self):
        """Regression: a plan resolved for one topology must never be
        silently reused for another (the memo key now carries the mesh
        signature and device count)."""
        sampling.reset_plans()
        p_flat = sampling.plan((32, 64), method="two_level", W=8)
        p_mesh = sampling.plan((32, 64), method="two_level", W=8, mesh=_mesh1())
        assert p_mesh is not p_flat
        assert p_mesh.mesh is not None and p_flat.mesh is None
        # same topology -> memo hit, not a re-resolution
        before = sampling.plan_stats()["plan_misses"]
        again = sampling.plan((32, 64), method="two_level", W=8, mesh=_mesh1())
        assert again is p_mesh
        assert sampling.plan_stats()["plan_misses"] == before
        # per-shard tag without a mesh is distinct from both
        p_dev = sampling.plan((32, 64), method="two_level", W=8, devices=4)
        assert p_dev is not p_flat and p_dev.devices == 4

    @pytest.mark.parametrize("method", ["two_level", "kernel", "gumbel", "alias"])
    def test_singledev_mesh_draw_matches_counter_semantics(self, method):
        r = np.random.default_rng(11)
        B, K = 24, 72
        w = jnp.array(r.uniform(0.1, 1.0, (B, K)).astype(np.float32))
        key = jax.random.PRNGKey(13)
        mesh = _mesh1()
        p = sampling.plan((B, K), method=method, W=8, mesh=mesh)
        out = np.array(p.sample(w, key=key))
        assert out.shape == (B,) and (out >= 0).all() and (out < K).all()
        # build+draw decomposition agrees with the fused one-shot
        dist = p.build(w)
        np.testing.assert_array_equal(out, np.array(p.draw(dist, key=key)))
        # u-driven variants: the counter semantics are the contract
        if method in ("two_level", "kernel"):
            from repro.sampling import distribution as _dist

            seed = rng.fold(
                rng.seed_from_key(key), rng.TAG_U, 0
            )
            u = rng.row_uniforms(seed, 0, B)
            flat = sampling.Categorical.from_weights(w, method=method, W=8)
            np.testing.assert_array_equal(
                out, np.array(_dist._draw_with_u(flat, u))
            )

    def test_sharded_draw_rejects_shape_mismatch(self):
        """Regression: a distribution of the wrong shape must error, not
        silently overlap global row counters across shards."""
        p = sampling.plan((16, 32), method="two_level", W=8, mesh=_mesh1())
        other = sampling.Categorical.from_weights(
            jnp.ones((8, 32), jnp.float32), method="two_level", W=8
        )
        with pytest.raises(ValueError, match="overlap"):
            p.draw(other, key=jax.random.PRNGKey(0))

    def test_sharded_draw_rejects_factored_dist(self):
        """Regression: a globally built factored distribution must not be
        row-sharded (its doc_ids index global theta rows)."""
        r = np.random.default_rng(14)
        C, N, V, K = 2, 8, 10, 32
        theta = jnp.array(r.uniform(0.5, 1.5, (C, K)).astype(np.float32))
        phi = jnp.array(r.uniform(0.5, 1.5, (V, K)).astype(np.float32))
        words = jnp.array(r.integers(0, V, C * N), jnp.int32)
        dist = sampling.Categorical.from_factors(
            theta, phi, words, jnp.arange(C * N, dtype=jnp.int32) // N, W=8
        )
        p = sampling.plan((C * N, K), method="two_level", W=8, mesh=_mesh1())
        with pytest.raises(ValueError, match="per shard"):
            p.draw(dist, key=jax.random.PRNGKey(0))

    def test_sharded_factored_sample_raises_at_boundary(self):
        p = sampling.plan(
            (16, 32), method="lda_kernel", W=8, factored=True, mesh=_mesh1()
        )
        with pytest.raises(ValueError, match="build_from_factors"):
            p.sample(jnp.ones((16, 32), jnp.float32),
                     key=jax.random.PRNGKey(0))

    def test_gumbel_sharded_logits_stay_in_logit_space(self):
        """Regression: the sharded gumbel serving path must not round-trip
        logits through exp — a token far below the row max keeps a finite
        log-weight instead of collapsing to -inf."""
        B, V = 8, 16
        logits = jnp.zeros((B, V), jnp.float32).at[:, 1:].add(-200.0)
        p = sampling.plan((B, V), method="gumbel", mesh=_mesh1())
        key = jax.random.PRNGKey(17)
        a = np.array(p.sample_logits(logits, key, temperature=1.0))
        np.testing.assert_array_equal(
            a, np.array(p.sample_logits(logits, key, temperature=1.0))
        )
        np.testing.assert_array_equal(a, np.zeros(B, np.int32))

    def test_spec_override_controls_row_axes(self):
        """spec= genuinely overrides the row axes (not just the memo key):
        invalid specs are rejected, and a spec naming an explicit axis
        draws identically to the default on the same mesh."""
        from jax.sharding import PartitionSpec

        mesh = _mesh1()
        with pytest.raises(ValueError, match="not on the mesh"):
            sampling.plan((8, 16), method="two_level", W=8, mesh=mesh,
                          spec=PartitionSpec("nope"))
        with pytest.raises(ValueError, match="axis 0"):
            sampling.plan((8, 16), method="two_level", W=8, mesh=mesh,
                          spec=PartitionSpec(None, "data"))
        r = np.random.default_rng(15)
        w = jnp.array(r.uniform(0.1, 1.0, (8, 16)).astype(np.float32))
        key = jax.random.PRNGKey(5)
        p_default = sampling.plan((8, 16), method="two_level", W=8, mesh=mesh)
        p_spec = sampling.plan((8, 16), method="two_level", W=8, mesh=mesh,
                               spec=PartitionSpec("data"))
        np.testing.assert_array_equal(
            np.array(p_default.sample(w, key=key)),
            np.array(p_spec.sample(w, key=key)),
        )

    def test_hw_rng_rejected_on_two_pass_fallback(self, monkeypatch):
        """hw=True must error, not silently switch RNG streams, when the
        fused tile overflows VMEM and the two-pass route takes over."""
        from repro.kernels.butterfly_sample import kernel as bk

        monkeypatch.setattr(bk, "_FUSED_TILE_BYTES", 256)
        w = jnp.ones((8, 128), jnp.float32)
        seed = rng.seed_from_key(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="hw_rng"):
            kops.butterfly_sample_rng(w, seed, W=8, hw=True)

    def test_sharded_draw_rejects_u(self):
        p = sampling.plan((8, 16), method="two_level", W=8, mesh=_mesh1())
        w = jnp.ones((8, 16), jnp.float32)
        with pytest.raises(ValueError, match="counter RNG"):
            p.sample(w, u=jnp.full((8,), 0.5))

    def test_sample_logits_sharded_deterministic(self):
        r = np.random.default_rng(12)
        B, V = 16, 64
        logits = jnp.array(r.normal(size=(B, V)).astype(np.float32))
        p = sampling.plan((B, V), method="two_level", W=8, mesh=_mesh1())
        key = jax.random.PRNGKey(21)
        a = np.array(p.sample_logits(logits, key, temperature=0.7))
        b = np.array(p.sample_logits(logits, key, temperature=0.7))
        np.testing.assert_array_equal(a, b)
        multi = np.array(
            p.sample_logits(logits, key, temperature=0.7, num_samples=3)
        )
        assert multi.shape == (3, B)
        greedy = np.array(p.sample_logits(logits, key, temperature=0.0))
        np.testing.assert_array_equal(greedy, np.argmax(np.array(logits), -1))


# ---------------------------------------------------------------------------
# Mesh helpers (the launch satellite)
# ---------------------------------------------------------------------------


class TestMeshHelpers:
    def test_make_host_mesh_error_is_descriptive(self):
        from repro.launch.mesh import make_host_mesh

        bad = len(jax.devices()) + 1  # never divides the device count
        with pytest.raises(ValueError, match="not divisible"):
            make_host_mesh(model=bad)
        with pytest.raises(ValueError, match="not divisible"):
            make_host_mesh(model=0)

    def test_smallest_fitting_mesh(self):
        from repro.launch.mesh import smallest_fitting_mesh

        m = smallest_fitting_mesh(1, 1)
        assert m.axis_names == ("data", "model")
        assert dict(m.shape) == {"data": 1, "model": 1}
        with pytest.raises(ValueError, match="needs"):
            smallest_fitting_mesh(len(jax.devices()) + 1, 1)
        with pytest.raises(ValueError, match="positive"):
            smallest_fitting_mesh(0, 1)


# ---------------------------------------------------------------------------
# 8 virtual devices (subprocess): invariance + the jaxpr collective gates
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro import sampling

    out = {}
    r = np.random.default_rng(0)
    B, K = 64, 96
    w = jnp.array(r.uniform(0.1, 1.0, (B, K)).astype(np.float32))
    key = jax.random.PRNGKey(7)
    for method in ("two_level", "kernel"):
        draws = {}
        for n in (1, 2, 8):
            mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
            p = sampling.plan((B, K), method=method, W=8, mesh=mesh)
            ws = sampling.sharded.place_rows(mesh, w)
            single = np.array(p.sample(ws, key=key))
            multi = np.array(p.draw(p.build(ws), key=key, num_samples=3))
            assert (multi[0] == single).all(), (method, n)
            draws[n] = (single.tolist(), multi.tolist())
        out[f"invariant_{method}"] = (
            draws[1] == draws[2] == draws[8]
        )

    # a batch that doesn't divide over the mesh is a descriptive error
    mesh8 = Mesh(np.array(jax.devices()), ("data",))
    try:
        sampling.plan((33, 64), method="two_level", mesh=mesh8)
        out["divisible_error"] = False
    except ValueError as e:
        out["divisible_error"] = "not divisible" in str(e)

    # jaxpr gate 1: the sharded draw path has ZERO collectives
    p = sampling.plan((B, K), method="two_level", W=8, mesh=mesh8)
    txt = str(jax.make_jaxpr(lambda ww, k: p.sample(ww, key=k))(w, key))
    out["draw_collectives"] = [
        c for c in ("all_gather", "all_to_all", "ppermute", "psum")
        if c in txt
    ]

    # jaxpr gate 2: the distributed Gibbs sweep has exactly ONE psum
    # (the AD-LDA word-topic all-reduce) and nothing else
    from repro.lda import init_state, perplexity, synthesize_corpus
    from repro.lda.distributed import make_sharded_gibbs

    Kt = 8
    corpus = synthesize_corpus(seed=0, M=64, V=80, K=Kt, avg_len=20,
                               max_len=32)
    state = init_state(jax.random.PRNGKey(1), corpus, Kt)
    p0 = perplexity(state, corpus)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    place, step = make_sharded_gibbs(mesh, K=Kt, V=corpus.vocab_size)
    with mesh:
        state, docs, mask = place(state, corpus.docs, corpus.mask)
        sweep_txt = str(jax.make_jaxpr(step)(state, docs, mask))
        out["sweep_psums"] = sweep_txt.count("psum[")
        out["sweep_collectives"] = [
            c for c in ("all_gather", "all_to_all", "ppermute")
            if c in sweep_txt
        ]
        for _ in range(12):
            state = step(state, docs, mask)
    from repro.lda import LDAState
    host = LDAState(*[jax.device_get(x) for x in state])
    out["p0"] = float(p0)
    out["p1"] = float(perplexity(host, corpus))
    out["theta_spec"] = str(state.theta.sharding.spec)
    out["phi_spec"] = str(state.phi.sharding.spec)
    out["phi_replicated"] = state.phi.sharding.is_fully_replicated

    # mesh helpers on a real multi-device host
    from repro.launch.mesh import make_host_mesh, smallest_fitting_mesh
    out["host_mesh"] = dict(make_host_mesh(model=2).shape)
    out["small_mesh"] = dict(smallest_fitting_mesh(2, 1).shape)
    print(json.dumps(out))
    """
)


@pytest.mark.slow
def test_sharded_8_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # device-count invariance: 1 == 2 == 8 for the same key
    assert res["invariant_two_level"], res
    assert res["invariant_kernel"], res
    assert res["divisible_error"] is True, res
    # the acceptance gates: no collectives on the draw path; exactly the
    # counts all-reduce in the sweep
    assert res["draw_collectives"] == [], res
    assert res["sweep_psums"] == 1, res
    assert res["sweep_collectives"] == [], res
    # the sweep still learns, sharded as declared
    assert res["p1"] < 0.8 * res["p0"], res
    assert "data" in res["theta_spec"], res
    assert res["phi_replicated"] is True, res  # spec P() or P(None, None)
    assert res["host_mesh"] == {"data": 4, "model": 2}
    assert res["small_mesh"] == {"data": 2, "model": 1}
