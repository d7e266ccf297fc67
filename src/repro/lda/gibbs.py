"""Uncollapsed LDA Gibbs sampler (paper §2, Algorithm 1/4/7).

One sweep =
  1. DRAW Z  — for every word position (m, i): build the K relative
     probabilities ``theta[m,k] * phi[w[m,i],k]`` and draw a topic.  This
     is the paper's hot loop; the sampling strategy is pluggable
     (``auto`` — the default, which ``sampling.plan``'s rule resolves to
     the factored ``lda_kernel`` — or a fixed ``lda_kernel`` /
     ``butterfly`` / ``fenwick`` / ``two_level`` / ``kernel`` / ``prefix``
     / ``gumbel``).
  2. UPDATE THETA — theta[m,:] ~ Dirichlet(alpha + doc-topic counts).
  3. UPDATE PHI   — phi[:,k]  ~ Dirichlet(beta + word-topic counts).

The default sweep is FUSED and ZERO-MATERIALIZATION: ``gibbs_step``
compiles the whole sweep (z-draw + counts + theta/phi resample) as one
jitted function whose z-draw is a single ``lax.scan`` over document
chunks — no Python chunk loop, no per-chunk dispatch — with the old
``theta``/``z`` buffers donated to XLA on accelerator backends (they are
dead after the draw, so the sweep updates in place).  When the strategy
resolves to the factored ``lda_kernel`` path (what ``auto`` picks for
this workload), each chunk's draw consumes the (theta, phi) factors
directly — one fused Pallas kernel on TPU, the pure-XLA twin elsewhere —
and the ``(chunk*maxN, K)`` weight tensor NEVER exists (DESIGN.md §4).
Non-factored strategies materialize only one chunk's weights at a time
inside the scan body.

Passing ``dists=`` (a mutable mapping chunk-start -> ``Categorical``)
selects the legacy per-chunk Python loop instead: each chunk's built
distribution is kept across sweeps and *refreshed* in place —
``refresh_from_factors`` for the factored variant, ``refreshed`` for the
flat-table variants — so the last sweep's tables remain available for
posterior draws.

For the multi-host layout, documents shard over the ``data`` mesh axis
and the word-topic count matrix is combined with a psum (see
``repro.launch.train --app lda``).

NOTE on donation: on non-CPU backends the fused sweep donates the
incoming ``state.theta`` and ``state.z`` buffers — after ``gibbs_step``
returns, the *old* state's theta/z must not be read again (rebind the
returned state, as every caller here does).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs, sampling
from repro.lda.corpus import Corpus


class LDAState(NamedTuple):
    theta: jnp.ndarray  # (M, K) document-topic distributions (rows sum to 1)
    phi: jnp.ndarray    # (V, K) word-topic distributions (columns sum to 1)
    z: jnp.ndarray      # (M, maxN) int32 latent topic assignments
    key: jax.Array
    step: jnp.ndarray   # () int32


def init_state(key: jax.Array, corpus: Corpus, K: int) -> LDAState:
    M, maxN = corpus.docs.shape
    V = corpus.vocab_size
    k1, k2, k3, k4 = jax.random.split(key, 4)
    theta = jax.random.dirichlet(k1, jnp.ones((K,)), shape=(M,))
    phi = jax.random.dirichlet(k2, jnp.ones((V,)), shape=(K,)).T
    z = jax.random.randint(k3, (M, maxN), 0, K)
    return LDAState(theta=theta, phi=phi, z=z, key=k4, step=jnp.int32(0))


@jax.jit
def _chunk_weights(theta_c, phi, docs_c):
    """weights[c, i, k] = theta[c, k] * phi[docs[c, i], k]  (paper Alg. 1 l.8)."""
    return theta_c[:, None, :] * phi[docs_c]                # (C, N, K)


def _chunk_plan(B: int, K: int, method: str, W, dtype: str) -> sampling.SamplerPlan:
    """Plan a (B, K) chunk draw as a *factored* workload — the gibbs
    workload always arrives as a theta-phi product, so ``auto`` picks the
    fused ``lda_kernel`` path."""
    # gumbel consumes the PRNG key directly; every other strategy draws
    # from key-derived uniforms
    has_key = method in ("gumbel", "alias")
    return sampling.plan(
        (B, K), method=method, W=W, dtype=dtype, has_key=has_key, factored=True
    )


def _draw_chunk(theta_c, phi, docs_c, key, method: str, W) -> jnp.ndarray:
    """Draw z for one (C, N) chunk — the scan body.  Factored strategies
    never materialize the (C*N, K) weights; flat strategies materialize
    one chunk's worth inside this (fused, jitted) body only."""
    C, N = docs_c.shape
    K = theta_c.shape[-1]
    words = docs_c.reshape(-1)
    p = _chunk_plan(C * N, K, method, W, str(theta_c.dtype))
    if p.method in sampling.FACTORED_VARIANTS:
        from repro.kernels.lda_draw import lda_draw_factored

        doc_ids = jnp.arange(C * N, dtype=jnp.int32) // N
        u = jax.random.uniform(key, (C * N,), dtype=jnp.float32)
        idx = lda_draw_factored(
            theta_c, phi, doc_ids, words, u, W=p.W, tb=p.tb or 8
        )
        return idx.reshape(C, N)
    flat = _chunk_weights(theta_c, phi, docs_c).reshape(C * N, K)
    dist = p.build(flat)
    return p.draw(dist, key=key).reshape(C, N)


def _scan_draw(theta, phi, docs, key, method: str, W, chunk: int) -> jnp.ndarray:
    """The zero-materialization chunked z-draw: ONE ``lax.scan`` over
    document chunks (vs. the old Python loop with a host round-trip and a
    full (C, N, K) weight build per chunk)."""
    M, maxN = docs.shape
    K = theta.shape[-1]
    chunk = min(chunk, M) if M else chunk
    nc = max(1, -(-M // chunk))
    pad = nc * chunk - M
    if pad:
        docs = jnp.pad(docs, ((0, pad), (0, 0)))
        theta = jnp.pad(theta, ((0, pad), (0, 0)))
    # same key schedule as the legacy per-chunk loop (bit-compatible)
    keys = jax.random.split(key, nc + 1)[:nc]
    xs = (
        theta.reshape(nc, chunk, K),
        docs.reshape(nc, chunk, maxN),
        keys,
    )

    def body(carry, x):
        theta_c, docs_c, k = x
        return carry, _draw_chunk(theta_c, phi, docs_c, k, method, W)

    _, zs = jax.lax.scan(body, None, xs)
    return zs.reshape(nc * chunk, maxN)[:M]


# jitted sweep / draw executables, keyed by the static draw config.
# donate_argnums differs per backend (CPU ignores donation), hence the
# explicit cache instead of a bare @jax.jit.
_JIT_CACHE: Dict[Tuple, Callable] = {}


def _donate() -> bool:
    return jax.default_backend() != "cpu"


def _scan_draw_jit(method: str, W, chunk: int) -> Callable:
    # NO donation here: draw_z is public and returns only z, so the
    # caller's state.theta must stay readable.  Buffer donation happens
    # one level up, in the fused sweep, which hands back a full
    # replacement LDAState.
    key = ("draw", method, W, chunk)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(
            functools.partial(_scan_draw, method=method, W=W, chunk=chunk)
        )
        _JIT_CACHE[key] = fn
    return fn


def _sweep_jit(method: str, W, chunk: int, K: int, V: int) -> Callable:
    donate = _donate()
    key = ("sweep", method, W, chunk, K, V, donate)
    fn = _JIT_CACHE.get(key)
    if fn is None:

        def impl(theta, phi, z_old, rng, step, docs, mask, alpha, beta):
            del z_old  # donated: its buffer backs the new z
            z = _scan_draw(theta, phi, docs, rng, method, W, chunk)
            doc_topic, word_topic = _counts(z, docs, mask, K, V)
            k_theta, k_phi, k_next = jax.random.split(rng, 3)
            new_theta = _update_theta(k_theta, doc_topic, alpha)
            new_phi = _update_phi(k_phi, word_topic, beta)
            return LDAState(
                theta=new_theta, phi=new_phi, z=z, key=k_next, step=step + 1
            )

        fn = jax.jit(impl, donate_argnums=(0, 2) if donate else ())
        _JIT_CACHE[key] = fn
    return fn


def _draw_z_chunk(
    theta_c, phi, docs_c, key, method="auto", W=None,
    dist: Optional[sampling.Categorical] = None,
):
    """Legacy per-chunk draw with cross-sweep distribution reuse.
    Returns ((C, N) topics, dist).

    Builds (or refreshes) the chunk's ``Categorical`` from this sweep's
    theta/phi and draws through the memoized plan's compiled path.
    Factored variants refresh via ``refresh_from_factors`` — new factor
    leaves, no (C*N, K) weights; flat variants via ``refreshed``."""
    C, N = docs_c.shape
    K = theta_c.shape[-1]
    p = _chunk_plan(C * N, K, method, W, str(theta_c.dtype))
    if p.method in sampling.FACTORED_VARIANTS:
        words = docs_c.reshape(-1)
        if (
            dist is not None
            and dist.method == p.method
            and dist.W == p.W
            and dist.shape == (C * N, K)
        ):
            dist = dist.refresh_from_factors(theta_c, phi, words)
        else:
            dist = p.build_from_factors(theta_c, phi, words)
        return p.draw(dist, key=key).reshape(C, N), dist
    flat = _chunk_weights(theta_c, phi, docs_c).reshape(C * N, K)
    if (
        dist is not None
        and dist.method == p.method
        and dist.W == p.W
        and dist.shape == tuple(flat.shape)
    ):
        dist = dist.refreshed(flat)
    else:
        # no reusable dist (first sweep, or the chunking/method changed
        # under a held dists cache): build fresh rather than refresh
        dist = p.build(flat)
    idx = p.draw(dist, key=key)
    return idx.reshape(C, N), dist


def draw_z(
    state: LDAState,
    docs: jnp.ndarray,
    method: str = "auto",
    W: int = None,
    chunk: int = 256,
    dists: Optional[Dict[int, sampling.Categorical]] = None,
) -> jnp.ndarray:
    """Chunked z-draw over all documents.

    Default (``dists=None``): one jitted ``lax.scan`` over chunks — the
    zero-materialization path.  (No buffer donation here: ``state``
    remains fully readable after the call; the donating path is the
    fused sweep in ``gibbs_step``, which returns a replacement state.)

    ``dists``: optional mutable mapping chunk-start -> ``Categorical``.
    When provided, the legacy Python chunk loop runs instead and each
    chunk's built distribution is kept there across sweeps and refreshed
    in place (the paper's reuse pattern), at the cost of materializing
    flat weights for the non-factored strategies."""
    with obs.span("lda.draw_z"):
        if dists is None:
            return _scan_draw_jit(method, W, chunk)(
                state.theta, state.phi, docs, state.key
            )
        M, maxN = docs.shape
        keys = jax.random.split(state.key, (M + chunk - 1) // chunk + 1)
        outs = []
        for ci, start in enumerate(range(0, M, chunk)):
            end = min(start + chunk, M)
            idx, dist = _draw_z_chunk(
                state.theta[start:end],
                state.phi,
                docs[start:end],
                keys[ci],
                method=method,
                W=W,
                dist=dists.get(start),
            )
            if dist is not None:
                dists[start] = dist
            outs.append(idx)
        return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("K", "V"))
def _counts(z, docs, mask, K: int, V: int):
    zoh = jax.nn.one_hot(z, K, dtype=jnp.float32) * mask[..., None]  # (M,N,K)
    doc_topic = zoh.sum(axis=1)                                       # (M,K)
    word_topic = jnp.zeros((V, K), jnp.float32).at[docs.reshape(-1)].add(
        zoh.reshape(-1, K)
    )
    return doc_topic, word_topic


@jax.jit
def _update_theta(key, doc_topic, alpha):
    g = jax.random.gamma(key, alpha + doc_topic)          # (M, K)
    return g / g.sum(axis=-1, keepdims=True)


@jax.jit
def _update_phi(key, word_topic, beta):
    g = jax.random.gamma(key, beta + word_topic)          # (V, K)
    return g / g.sum(axis=0, keepdims=True)


def gibbs_step(
    state: LDAState,
    corpus: Corpus,
    alpha: float = 0.1,
    beta: float = 0.05,
    method: str = "auto",
    W: int = None,
    chunk: int = 256,
    dists: Optional[Dict[int, sampling.Categorical]] = None,
    sparse: bool = False,
    sparse_cache=None,
    mh_steps: int = 2,
    word_proposal: str = "cdf",
) -> LDAState:
    """One full uncollapsed Gibbs sweep.

    Default: the fused jitted sweep (scanned z-draw + counts + Dirichlet
    resamples in one executable; old theta/z buffers donated off-CPU).
    Pass the same dict as ``dists=`` on every call to instead hold the
    per-chunk ``Categorical`` distributions across sweeps (refreshed each
    sweep from the new theta/phi).

    ``sparse=True`` routes the sweep through ``repro.lda.sparse`` — the
    sparsity-aware MH-alias z-draw whose per-token cost is sublinear in K
    (same ``LDAState`` in/out, exact same target distribution).
    Pass the same ``sparse_cache=``
    (a ``repro.lda.sparse.SparseSweepCache``) on every call so the
    fixed-width sparse doc-topic counts persist across sweeps;
    ``mh_steps``/``word_proposal`` tune the MH chain (see
    ``sparse.gibbs_step_sparse``).

    The host call is an ``lda.sweep`` span (``repro.obs``), indexed by the
    ``lda.sweeps`` counter, with ``lda.upload`` (corpus to the device) and
    ``lda.dispatch`` (the sweep's programs) children."""
    if sparse:
        from repro.lda import sparse as _sparse

        return _sparse.gibbs_step_sparse(
            state, corpus, alpha=alpha, beta=beta, mh_steps=mh_steps,
            word_proposal=word_proposal, cache=sparse_cache, chunk=chunk,
        )
    K = state.theta.shape[-1]
    V = state.phi.shape[0]
    with obs.span("lda.sweep", index=obs.count("lda.sweeps") - 1):
        with obs.span("lda.upload"):
            docs = jnp.asarray(corpus.docs)
            mask = jnp.asarray(corpus.mask)
        with obs.span("lda.dispatch"):
            if dists is None:
                return _sweep_jit(method, W, chunk, K, V)(
                    state.theta, state.phi, state.z, state.key, state.step,
                    docs, mask, jnp.float32(alpha), jnp.float32(beta),
                )
            z = draw_z(state, docs, method=method, W=W, chunk=chunk, dists=dists)
            doc_topic, word_topic = _counts(z, docs, mask, K, V)
            k_theta, k_phi, k_next = jax.random.split(state.key, 3)
            theta = _update_theta(k_theta, doc_topic, alpha)
            phi = _update_phi(k_phi, word_topic, beta)
    return LDAState(theta=theta, phi=phi, z=z, key=k_next, step=state.step + 1)


@jax.jit
def log_likelihood(theta, phi, docs, mask) -> jnp.ndarray:
    """Held-in predictive log likelihood sum_{m,i} log sum_k theta*phi."""
    p = jnp.einsum("mk,mnk->mn", theta, phi[docs])
    ll = jnp.where(mask, jnp.log(jnp.maximum(p, 1e-30)), 0.0)
    return ll.sum()


def perplexity(state: LDAState, corpus: Corpus) -> float:
    ll = log_likelihood(
        state.theta, state.phi, jnp.asarray(corpus.docs), jnp.asarray(corpus.mask)
    )
    return float(jnp.exp(-ll / corpus.total_words))
