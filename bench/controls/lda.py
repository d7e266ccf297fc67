"""The LDA control: the reference sweep put in the program's place and held
in bfloat16 (theta and phi rounded to bfloat16 before the draw and after
the resample), the step below the configuration's float32 that would tempt
a later change.  ``patch()`` swaps it in for ``repro.lda.gibbs.gibbs_step``."""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

from bench.harness import load_module


def control_step(state, corpus, *, alpha, beta, chunk, sparse=False,
                 sparse_cache=None, mh_steps=0, **_):
    from repro.lda.gibbs import LDAState

    ref = load_module("refs", "lda")
    docs, mask = jnp.asarray(corpus.docs), jnp.asarray(corpus.mask)
    K, V = state.theta.shape[1], state.phi.shape[0]
    bf16 = jnp.bfloat16
    if sparse:
        cap = ref.sparse_caps([state.z], docs, mask, K, V,
                              sparse_cache.cap_min, sparse_cache.cap_max)[0]
        z = ref.mh_draw(state.z, docs, mask, state.theta, state.phi, state.key, alpha,
                        cap=cap, steps=mh_steps, chunk=chunk, dtype=bf16)
    else:
        z = ref.draw(state.theta, state.phi, state.key, docs, chunk=chunk, dtype=bf16)
    theta, phi, k_next = ref.resample(z, docs, mask, state.key, jnp.float32(alpha),
                                      jnp.float32(beta), K=K, V=V, dtype=bf16,
                                      sparse=bool(sparse))
    return LDAState(theta=theta, phi=phi, z=z, key=k_next, step=state.step + 1)


@contextlib.contextmanager
def patch():
    from repro.lda import gibbs

    orig = gibbs.gibbs_step
    gibbs.gibbs_step = control_step
    try:
        yield
    finally:
        gibbs.gibbs_step = orig
