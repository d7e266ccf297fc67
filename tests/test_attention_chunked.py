"""Chunked (flash-style) attention must equal the dense path exactly, and
the grouped-query dense path must equal attention over repeated K/V."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


# repro.dist.sharding at runtime)

from repro.models.attention import (
    _sdpa,
    _sdpa_chunked,
    attention_mask,
)


def _mk(B=2, Sq=50, Sk=50, H=4, KV=2, hd=8, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.array(rng.normal(size=(B, Sq, H, hd)), jnp.float32)
    k = jnp.array(rng.normal(size=(B, Sk, KV, hd)), jnp.float32)
    v = jnp.array(rng.normal(size=(B, Sk, KV, hd)), jnp.float32)
    return q, k, v


def test_chunked_matches_dense_causal():
    q, k, v = _mk()
    pos = jnp.arange(50)
    dense = _sdpa(q, k, v, attention_mask(pos, pos, causal=True), 0.0)
    chunk = _sdpa_chunked(q, k, v, pos, pos, causal=True, window=0, q_chunk=16)
    np.testing.assert_allclose(np.array(dense), np.array(chunk), rtol=2e-5, atol=2e-5)


def test_chunked_matches_dense_window_softcap():
    q, k, v = _mk(seed=1)
    pos = jnp.arange(50)
    dense = _sdpa(q, k, v, attention_mask(pos, pos, causal=True, window=7), 30.0)
    chunk = _sdpa_chunked(
        q, k, v, pos, pos, causal=True, window=7, softcap=30.0, q_chunk=16
    )
    np.testing.assert_allclose(np.array(dense), np.array(chunk), rtol=2e-5, atol=2e-5)


def test_chunked_nondivisible_and_kvalid():
    q, k, v = _mk(Sq=37, Sk=41, seed=2)
    qpos, kpos = jnp.arange(37), jnp.arange(41)
    kv_mask = kpos < 30
    dense = _sdpa(q, k, v, attention_mask(qpos, kpos, causal=False, k_valid=kv_mask), 0.0)
    chunk = _sdpa_chunked(
        q, k, v, qpos, kpos, causal=False, window=0, k_valid=kv_mask, q_chunk=16
    )
    np.testing.assert_allclose(np.array(dense), np.array(chunk), rtol=2e-5, atol=2e-5)


def test_grad_flows_through_chunked():
    q, k, v = _mk(seed=3)
    pos = jnp.arange(50)

    def f(q, k, v):
        return jnp.sum(
            _sdpa_chunked(q, k, v, pos, pos, causal=True, window=0, q_chunk=16) ** 2
        )

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    assert all(bool(jnp.isfinite(x).all()) for x in g)
    assert all(float(jnp.abs(x).max()) > 0 for x in g)


def _sdpa_repeat(q, k, v, mask, softcap):
    """Attention over K/V repeated to every query head: (B, H, Sq, Sk)
    scores, the form the grouped contraction replaces."""
    H = q.shape[2]
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.einsum("bqhe,bshe->bhqs", q, k).astype(jnp.float32) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    m = mask[None, None] if mask.ndim == 2 else mask[:, None]
    scores = jnp.where(m, scores, -2.0e38)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshv->bqhv", probs, v)


def _gqa_mask(kind, B, Sq, Sk, seed):
    if kind == "causal_window":
        pos = jnp.arange(Sq)
        return attention_mask(pos, pos, causal=True, window=5)
    # continuous-batching decode: one query per row, each row its own length
    lens = np.random.default_rng(seed).integers(1, Sk, size=B)
    return jnp.arange(Sk)[None, None, :] <= jnp.asarray(lens)[:, None, None]


@pytest.mark.parametrize("grad", [False, True], ids=["value", "grad"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mask_kind", ["causal_window", "per_row_decode"])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("KV", [1, 2, 8, 32])
def test_grouped_sdpa_matches_repeated_kv(KV, softcap, mask_kind, dtype, grad):
    """Values agree to float32 tolerance in both dtypes.  bf16 gradients
    cannot: the repeat's transpose sums a group's G cotangents in bf16,
    the grouped contraction inside the dot; so there both are held to the
    float32 gradients, within bf16's rounding (2**-5 of the largest)."""
    H, hd, B = 32, 8, 2
    Sq, Sk = (12, 12) if mask_kind == "causal_window" else (1, 20)
    q32, k32, v32 = _mk(B=B, Sq=Sq, Sk=Sk, H=H, KV=KV, hd=hd, seed=KV)
    mask = _gqa_mask(mask_kind, B, Sq, Sk, seed=KV)

    def run(fn, dt):
        q, k, v = (x.astype(dt) for x in (q32, k32, v32))
        if not grad:
            return (fn(q, k, v, mask, softcap),)

        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, mask, softcap).astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = run(_sdpa, dtype)
    if grad and dtype == "bfloat16":
        want = run(_sdpa_repeat, "float32")
        tols = [dict(rtol=0, atol=2**-5 * float(jnp.max(jnp.abs(w)))) for w in want]
    else:
        want = run(_sdpa_repeat, dtype)
        tols = [dict(rtol=2e-5, atol=2e-5)] * len(want)
    for g, w, tol in zip(got, want, tols):
        assert g.shape == w.shape and g.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), **tol
        )
