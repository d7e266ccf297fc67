"""LDA integration tests: the full Gibbs loop learns planted structure,
and all sampling strategies are interchangeable."""

import numpy as np
import jax
import pytest

from repro.lda import (
    gibbs_step,
    init_state,
    perplexity,
    synthesize_corpus,
    topic_recovery_score,
)


@pytest.fixture(scope="module")
def small_corpus():
    return synthesize_corpus(seed=0, M=96, V=120, K=8, avg_len=40, max_len=80)


def _loop_corpus_docs(seed, M, V, K, avg_len, max_len, zipf_exponent=None):
    """Reference: one ``rng.choice`` per document, then one per topic the
    document uses — the loop the bulk generator replaces."""
    from repro.lda.corpus import _topic_word_dirichlet

    rng = np.random.default_rng(seed)
    phi = _topic_word_dirichlet(rng, V, K, 0.08, zipf_exponent)
    theta = rng.dirichlet(np.full(K, 0.25), size=M)
    lengths = np.clip(rng.poisson(avg_len, size=M), 1, max_len)
    docs = np.zeros((M, int(lengths.max())), np.int32)
    for m in range(M):
        n = lengths[m]
        topics = rng.choice(K, size=n, p=theta[m])
        words = np.empty(n, np.int32)
        for k in np.unique(topics):
            sel = topics == k
            words[sel] = rng.choice(V, size=sel.sum(), p=phi[:, k])
        docs[m, :n] = words
    return docs


@pytest.mark.parametrize("kw", [
    dict(seed=0, M=96, V=120, K=8, avg_len=40, max_len=80),
    dict(seed=3, M=300, V=2000, K=64, avg_len=70.5, max_len=307,
         zipf_exponent=1.05),
])
def test_bulk_corpus_matches_per_document_loop(kw):
    """The bulk generator draws the same corpus, token for token, as the
    per-document loop for the same seed."""
    np.testing.assert_array_equal(
        synthesize_corpus(**kw).docs, _loop_corpus_docs(**kw)
    )


def test_corpus_stats(small_corpus):
    c = small_corpus
    assert c.docs.shape[0] == 96
    assert (c.lengths >= 1).all()
    assert c.mask.sum() == c.lengths.sum()
    assert c.docs.max() < c.vocab_size
    bks = c.buckets((32, 64, 307))
    assert sum(b.num_docs for b in bks) == c.num_docs
    assert all(b.docs.shape[1] <= e for b, e in zip(bks, (32, 64, 307)))


def test_perplexity_decreases(small_corpus):
    """The headline integration check: Gibbs sweeps reduce perplexity."""
    K = 8
    state = init_state(jax.random.PRNGKey(1), small_corpus, K)
    p0 = perplexity(state, small_corpus)
    for _ in range(30):
        state = gibbs_step(state, small_corpus, method="fenwick")
    p1 = perplexity(state, small_corpus)
    assert np.isfinite(p1)
    assert p1 < 0.6 * p0, (p0, p1)
    assert p1 < small_corpus.vocab_size  # sanity: better than uniform


def test_topic_recovery(small_corpus):
    K = 8
    state = init_state(jax.random.PRNGKey(2), small_corpus, K)
    base = topic_recovery_score(np.array(state.phi), small_corpus.true_phi)
    for _ in range(60):
        state = gibbs_step(state, small_corpus, method="fenwick")
    score = topic_recovery_score(np.array(state.phi), small_corpus.true_phi)
    assert score > base + 0.15, (base, score)


@pytest.mark.parametrize("method", ["butterfly", "fenwick", "kernel", "prefix", "gumbel"])
def test_methods_interchangeable(small_corpus, method):
    """Every sampling strategy must drive the same Gibbs dynamics."""
    K = 8
    state = init_state(jax.random.PRNGKey(3), small_corpus, K)
    p0 = perplexity(state, small_corpus)
    for _ in range(8):
        state = gibbs_step(state, small_corpus, method=method, W=8)
    p1 = perplexity(state, small_corpus)
    assert np.isfinite(p1) and p1 < p0


def test_state_shapes_and_simplex(small_corpus):
    K = 8
    state = init_state(jax.random.PRNGKey(4), small_corpus, K)
    state = gibbs_step(state, small_corpus)
    np.testing.assert_allclose(np.array(state.theta.sum(-1)), 1.0, rtol=1e-5)
    np.testing.assert_allclose(np.array(state.phi.sum(0)), 1.0, rtol=1e-4)
    z = np.array(state.z)
    assert ((z >= 0) & (z < K)).all()
    assert int(state.step) == 1
