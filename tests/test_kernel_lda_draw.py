"""Fused LDA z-draw kernel: shape/dtype sweep vs the pure-jnp oracle, and
end-to-end inside the Gibbs sampler."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.lda_draw import lda_draw
from repro.kernels.lda_draw.ref import lda_draw_ref


@pytest.mark.parametrize("W", [8, 16, 32])
@pytest.mark.parametrize("B,V,K", [(16, 50, 24), (32, 100, 19), (8, 40, 240), (64, 30, 7)])
def test_shape_sweep(W, B, V, K):
    rng = np.random.default_rng(B + V + K + W)
    theta = jnp.array(rng.integers(1, 100, size=(B, K)).astype(np.float32))
    phi = jnp.array(rng.integers(1, 100, size=(V, K)).astype(np.float32))
    words = jnp.array(rng.integers(0, V, size=(B,)), jnp.int32)
    u = jnp.array(rng.uniform(0, 1, size=(B,)).astype(np.float32))
    got = np.array(lda_draw(theta, phi, words, u, W=W))
    np.testing.assert_array_equal(got, np.array(lda_draw_ref(theta, phi, words, u)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(dtype):
    rng = np.random.default_rng(5)
    B, V, K = 24, 60, 32
    theta = jnp.array(rng.integers(1, 16, size=(B, K)).astype(np.float32)).astype(dtype)
    phi = jnp.array(rng.integers(1, 16, size=(V, K)).astype(np.float32)).astype(dtype)
    words = jnp.array(rng.integers(0, V, size=(B,)), jnp.int32)
    u = jnp.array(rng.uniform(0.05, 0.95, size=(B,)).astype(np.float32))
    got = np.array(lda_draw(theta, phi, words, u, W=8))
    ref = np.array(
        lda_draw_ref(theta.astype(jnp.float32), phi.astype(jnp.float32), words, u)
    )
    diff = np.abs(got - ref)
    assert (diff <= (0 if dtype == jnp.float32 else 1)).all()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), K=st.integers(2, 80), B=st.integers(1, 16))
def test_property_matches_oracle(seed, K, B):
    rng = np.random.default_rng(seed)
    V = 37
    theta = jnp.array(rng.integers(1, 2**12, size=(B, K)).astype(np.float32))
    phi = jnp.array(rng.integers(1, 2**12, size=(V, K)).astype(np.float32))
    words = jnp.array(rng.integers(0, V, size=(B,)), jnp.int32)
    u = jnp.array(rng.uniform(0, 1, size=(B,)).astype(np.float32))
    got = np.array(lda_draw(theta, phi, words, u, W=8))
    np.testing.assert_array_equal(got, np.array(lda_draw_ref(theta, phi, words, u)))


def test_gibbs_with_fused_kernel():
    from repro.lda import gibbs_step, init_state, perplexity, synthesize_corpus

    corpus = synthesize_corpus(seed=3, M=48, V=80, K=6, avg_len=30, max_len=60)
    state = init_state(jax.random.PRNGKey(0), corpus, 6)
    p0 = perplexity(state, corpus)
    for _ in range(6):
        state = gibbs_step(state, corpus, method="lda_kernel", W=8)
    p1 = perplexity(state, corpus)
    assert np.isfinite(p1) and p1 < p0


def test_long_sample_list_runs_in_smem_sized_chunks(monkeypatch):
    """A sample list longer than one launch's scalar-prefetch budget (a
    whole shard of a sharded sweep) draws the same indices in chunks."""
    from repro.kernels.lda_draw import kernel as lk
    from repro.kernels.lda_draw.ops import lda_draw_factored

    rng = np.random.default_rng(9)
    C, V, K, B = 12, 40, 24, 200
    theta = jnp.array(rng.integers(1, 100, size=(C, K)).astype(np.float32))
    phi = jnp.array(rng.integers(1, 100, size=(V, K)).astype(np.float32))
    docs = jnp.array(rng.integers(0, C, size=(B,)), jnp.int32)
    words = jnp.array(rng.integers(0, V, size=(B,)), jnp.int32)
    u = jnp.array(rng.uniform(0, 1, size=(B,)).astype(np.float32))
    whole = np.array(lk.lda_draw_docs_pallas(theta, phi, docs, words, u, W=8))
    monkeypatch.setattr(lk, "_SMEM_SAMPLES", 64)
    chunked = np.array(
        lda_draw_factored(theta, phi, docs, words, u, W=8, impl="pallas")
    )
    np.testing.assert_array_equal(chunked, whole)
    np.testing.assert_array_equal(
        whole, np.array(lda_draw_ref(theta[docs], phi, words, u))
    )
