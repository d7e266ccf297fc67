"""Sparse MH-alias LDA sweep (ISSUE 8): statistical equivalence to the
exact conditional, perplexity parity with the dense sweep, acceptance
sanity, pow2 capacity-bucket determinism, the no-(B,K)-weight jaxpr
gate, and the streaming million-doc path at toy scale."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.lda import (
    LDAState,
    SparseSweepCache,
    StreamingSparseLDA,
    draw_z_sparse,
    gibbs_step,
    gibbs_step_sparse,
    init_state,
    perplexity,
    sparse_counts,
    synthesize_corpus,
)
from repro.lda import sparse as lda_sparse
from repro.lda.corpus import zipf_shard_source

from test_sampler_stats import CHI2_999, _chi2_stat
from test_tiled_kernels import _all_avals


# ---------------------------------------------------------------------------
# Statistical equivalence: the MH chain's per-token law -> exact conditional
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["alias", "cdf"])
def test_mh_marginals_match_exact_conditional(mode):
    """Every token shares one (theta row, word), so every MH chain
    targets the same p(k) ~ theta0[k] * phi[0, k]; after dozens of
    cycles the pooled z marginal must pass chi-square against it.
    Truncated sparse counts (cap << K_d is fine) must NOT break this —
    exactness is by construction, not by capacity."""
    M, L, K, V = 128, 64, 16, 48
    rng = np.random.default_rng(3)
    theta0 = rng.dirichlet(np.full(K, 0.5))
    phi = np.ascontiguousarray(rng.dirichlet(np.full(V, 0.3), size=K).T)
    theta = jnp.tile(jnp.asarray(theta0, jnp.float32)[None], (M, 1))
    docs = jnp.zeros((M, L), jnp.int32)             # every token is word 0
    mask = jnp.ones((M, L), bool)
    z0 = jnp.asarray(rng.integers(0, K, size=(M, L)), jnp.int32)
    state = LDAState(
        theta=theta, phi=jnp.asarray(phi, jnp.float32), z=z0,
        key=jax.random.PRNGKey(7), step=jnp.int32(0),
    )
    z = draw_z_sparse(
        state, docs, mask, mh_steps=40, word_proposal=mode,
        cache=SparseSweepCache(cap_min=8, cap_max=8),  # deliberate truncation
    )
    counts = np.bincount(np.asarray(z).ravel(), minlength=K).astype(np.float64)
    probs = theta0 * phi[0]
    probs = probs / probs.sum()
    stat, dof = _chi2_stat(counts, probs)
    assert stat < CHI2_999[15], f"{mode}: chi2={stat:.1f} dof={dof}"


def test_perplexity_parity_with_dense_sweep():
    """After 10 sweeps from the same init, the sparse trainer's held-in
    perplexity is no more than 2% above the dense trainer's (same corpus,
    same hyperparameters — different but equally valid samplers)."""
    corpus = synthesize_corpus(5, M=96, V=128, K=8, avg_len=32, max_len=64)
    K = 16
    s_dense = init_state(jax.random.PRNGKey(0), corpus, K)
    s_sparse = init_state(jax.random.PRNGKey(0), corpus, K)
    cache = SparseSweepCache()
    for _ in range(10):
        s_dense = gibbs_step(s_dense, corpus)
        s_sparse = gibbs_step_sparse(s_sparse, corpus, mh_steps=4, cache=cache)
    p_dense = perplexity(s_dense, corpus)
    p_sparse = perplexity(s_sparse, corpus)
    # sparse must be no worse than dense (it may well be better)
    assert p_sparse < 1.02 * p_dense, (p_dense, p_sparse)


def test_acceptance_rates_sane():
    """MH acceptance on a mixing chain is high but not degenerate-zero:
    both proposal kinds must land in (0.1, 1.0]."""
    corpus = synthesize_corpus(6, M=64, V=96, K=8, avg_len=24, max_len=48)
    state = init_state(jax.random.PRNGKey(2), corpus, 32)
    cache = SparseSweepCache()
    for _ in range(3):
        state = gibbs_step_sparse(state, corpus, mh_steps=2, cache=cache)
    stats = cache.last_stats
    assert stats is not None
    for kind in ("word_accept_rate", "doc_accept_rate"):
        assert 0.1 < stats[kind] <= 1.0, (kind, stats)


# ---------------------------------------------------------------------------
# Capacity bucketing
# ---------------------------------------------------------------------------


def test_pow2_capacity_buckets():
    assert lda_sparse.pow2_capacity(1) == 8          # cap_min clamp
    assert lda_sparse.pow2_capacity(8) == 8
    assert lda_sparse.pow2_capacity(9) == 16
    assert lda_sparse.pow2_capacity(33) == 64
    assert lda_sparse.pow2_capacity(1000) == 64      # cap_max clamp


def test_capacity_hysteresis():
    """Grow immediately on overflow; shrink only at 4x slack — so a
    noisy nnz sequence causes at most one retrace per real regime
    change."""
    c = SparseSweepCache()
    assert c.update_capacity(20) == 32
    assert c.update_capacity(40) == 64               # grow now
    assert c.update_capacity(20) == 64               # no shrink (20 > 64//4)
    assert c.update_capacity(16) == 16               # 16 <= 64//4: shrink
    assert c.caps_history == [32, 64, 16]


def test_sparse_sweep_deterministic_rerun():
    """Same state + fresh caches => bit-identical z trajectory and the
    same capacity-bucket history (regrowth is deterministic)."""
    corpus = synthesize_corpus(7, M=48, V=64, K=8, avg_len=24, max_len=48)
    state0 = init_state(jax.random.PRNGKey(4), corpus, 24)

    def run():
        cache = SparseSweepCache(cap_min=8, cap_max=32)
        s = state0
        for _ in range(3):
            s = gibbs_step_sparse(s, corpus, mh_steps=2, cache=cache)
        return np.asarray(s.z), list(cache.caps_history)

    z1, caps1 = run()
    z2, caps2 = run()
    assert caps1 == caps2
    np.testing.assert_array_equal(z1, z2)


def test_sparse_counts_truncates_to_largest():
    dt = jnp.asarray([[5, 0, 9, 1, 3, 0, 2, 7]], jnp.float32)
    sp = sparse_counts(dt, 4)
    assert sp.ids.shape == (1, 4) and sp.cnt.shape == (1, 4)
    assert sorted(np.asarray(sp.cnt)[0].tolist(), reverse=True) == [9, 7, 5, 3]


# ---------------------------------------------------------------------------
# The jaxpr gate: no (tokens, K) weight tensor anywhere in the sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("steps", [2, 8])  # unrolled and fori_loop paths
def test_mh_sweep_never_materializes_tokens_by_K(steps):
    """The sparse sweep's whole point: per-token work is O(cap + log K),
    so no intermediate in the jaxpr may reach tokens*K elements (the
    dense weight product).  V*K tables are fine — they're O(model), not
    O(corpus * model)."""
    M, L, K, V, cap, chunk = 64, 32, 64, 32, 8, 64
    tokens = M * L
    z = jnp.zeros((M, L), jnp.int32)
    docs = jnp.zeros((M, L), jnp.int32)
    mask = jnp.ones((M, L), bool)
    theta = jnp.ones((M, K), jnp.float32) / K
    phi = jnp.ones((V, K), jnp.float32) / V
    ids = jnp.zeros((M, cap), jnp.int32)
    cnt = jnp.ones((M, cap), jnp.int32)
    tbl_a = lda_sparse._phi_cdf(phi)
    tbl_b = jnp.zeros((1, 1), jnp.int32)

    import functools

    fn = functools.partial(
        lda_sparse._mh_sweep, steps=steps, cap=cap, mode="cdf", chunk=chunk
    )
    jaxpr = jax.make_jaxpr(fn)(
        z, docs, mask, theta, phi, ids, cnt, tbl_a, tbl_b,
        jnp.zeros(2, jnp.uint32), jnp.uint32(0), jnp.float32(0.1),
    )
    limit = tokens * K
    big = [a for a in _all_avals(jaxpr.jaxpr) if a.size >= limit]
    assert not big, f"materialized {[(a.shape, a.dtype) for a in big]}"


# ---------------------------------------------------------------------------
# Streaming sweep
# ---------------------------------------------------------------------------


def test_zipf_shard_source_deterministic():
    src = zipf_shard_source(0, num_docs=600, V=128, K=16, shard_docs=256,
                            avg_len=16, max_len=40)
    assert src.num_shards == 3
    d1, m1 = src.shard(0)
    d2, m2 = src.shard(0)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(m1, m2)
    dl, ml = src.shard(2)                        # partial final shard
    assert dl.shape == (88, 40) and ml.dtype == bool
    with pytest.raises(IndexError):
        src.shard(3)


def test_streaming_sweep_small():
    src = zipf_shard_source(1, num_docs=300, V=96, K=12, shard_docs=128,
                            avg_len=16, max_len=40)
    eng = StreamingSparseLDA(jax.random.PRNGKey(3), src, K=12, mh_steps=2,
                             cap=8, chunk=64)
    s1 = eng.sweep()
    s2 = eng.sweep()
    assert s1["tokens"] == s2["tokens"] > 0
    for s in (s1, s2):
        assert np.isfinite(s["perplexity"]) and s["perplexity"] > 1
        assert 0 < s["doc_accept_rate"] <= 1
    # training on a planted corpus must beat the uniform-vocab ceiling
    assert s2["perplexity"] < src.vocab_size


@pytest.mark.slow
def test_streaming_sweep_improves_perplexity():
    src = zipf_shard_source(2, num_docs=4096, V=512, K=64, shard_docs=1024,
                            avg_len=48, max_len=128)
    eng = StreamingSparseLDA(jax.random.PRNGKey(0), src, K=64, mh_steps=2)
    stats = [eng.sweep() for _ in range(5)]
    assert stats[-1]["perplexity"] < stats[0]["perplexity"]
    assert stats[-1]["tokens_per_sec"] > 0


# ---------------------------------------------------------------------------
# Integration: gibbs_step(sparse=)
# ---------------------------------------------------------------------------


def test_gibbs_step_sparse_flag_same_state_shape():
    corpus = synthesize_corpus(8, M=32, V=64, K=8, avg_len=16, max_len=32)
    state = init_state(jax.random.PRNGKey(1), corpus, 16)
    out = gibbs_step(state, corpus, sparse=True, mh_steps=1)
    assert isinstance(out, LDAState)
    assert out.theta.shape == state.theta.shape
    assert out.phi.shape == state.phi.shape
    assert out.z.shape == state.z.shape
    assert int(out.step) == int(state.step) + 1
