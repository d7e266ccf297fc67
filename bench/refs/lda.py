"""Plain reference of one uncollapsed LDA Gibbs sweep (arXiv:1505.03851 §2,
Algorithm 1), written from the paper and not from the code under test.

A sweep draws every token's topic from p(k) ~ theta[d, k] * phi[w, k] by the
inverse cdf at a uniform u, then counts topics per document and per word,
and resamples theta[d, :] ~ Dirichlet(alpha + n_dk) and
phi[:, k] ~ Dirichlet(beta + n_wk).  The uniforms and the gamma draws follow
the documented key schedule of ``repro.lda.gibbs_step``'s dense sweep, so the
reference and the program read the same random numbers:

  keys = split(key, chunks + 1)[:chunks]       one per chunk of ``chunk`` documents
  u    = uniform(keys[c], (chunk * maxN,))      float32, row-major in the chunk
  k_theta, k_phi, k_next = split(key, 3)

``chunk`` is the sweep's ``chunk`` argument, which the benchmark passes to
the program from the configuration file, so the stream it pins is data.

The sparse sweep (``gibbs_step(sparse=True)``, the WarpLDA/EZLDA MH-alias
chain of ``repro.lda.sparse``) is followed the same way: ``mh_steps`` cycles
of a word proposal (k' by the inverse cdf of phi[w, :], accepted when
u * theta[d, z] < theta[d, k']) and a doc proposal (k' from alpha + the
document's top-``cap`` topic counts of the previous sweep, accepted by the
Metropolis-Hastings ratio), with uniforms from Threefry-2x32 at counter
(global token id, 5 * cycle + use) under the seed (kz, 5), where
kz, k_theta, k_phi, k_next = split(key, 4).  The top-topics capacity is a
power of two in [cap_min, cap_max], as the program's ``SparseSweepCache``
is given them.

``dtype`` is the precision theta and phi are held in; float32 is the
configuration's, bfloat16 the control's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry_2x32

SPARSE_TAG = 5        # the sparse sweep's stream tag


@functools.partial(jax.jit, static_argnames=("chunk", "dtype"))
def draw(theta, phi, key, docs, chunk: int, dtype=jnp.float32):
    """(M, maxN) topics drawn by the inverse cdf of theta[d] * phi[w]."""
    M, N = docs.shape
    K = theta.shape[1]
    nc = -(-M // chunk)
    pad = nc * chunk - M
    docs_p = jnp.pad(docs, ((0, pad), (0, 0)))
    theta_p = jnp.pad(theta.astype(dtype), ((0, pad), (0, 0)))
    phi_d = phi.astype(dtype)
    keys = jax.random.split(key, nc + 1)[:nc]

    def one(x):
        theta_c, docs_c, k = x
        w = (theta_c[:, None, :] * phi_d[docs_c]).astype(jnp.float32)   # (C, N, K)
        cdf = jnp.cumsum(w, axis=-1)
        u = jax.random.uniform(k, (chunk * N,), jnp.float32).reshape(chunk, N)
        stop = cdf[..., -1] * u
        z = jnp.sum((cdf <= stop[..., None]).astype(jnp.int32), axis=-1)
        return jnp.minimum(z, K - 1)

    zs = jax.lax.map(one, (theta_p.reshape(nc, chunk, K),
                           docs_p.reshape(nc, chunk, N), keys))
    return zs.reshape(nc * chunk, N)[:M]


def counts(z, docs, mask, K: int, V: int):
    """(doc-topic (M, K), word-topic (V, K)) counts of the unmasked tokens."""
    M = z.shape[0]
    zm = jnp.where(mask, z, K)
    dt = jnp.zeros((M, K + 1), jnp.float32).at[jnp.arange(M)[:, None], zm].add(1.0)
    wt = jnp.zeros((V, K + 1), jnp.float32).at[docs, zm].add(1.0)
    return dt[:, :K], wt[:, :K]


@functools.partial(jax.jit, static_argnames=("K", "V", "dtype", "sparse"))
def resample(z, docs, mask, key, alpha, beta, K: int, V: int, dtype=jnp.float32,
             sparse: bool = False):
    """(theta, phi, next key): Dirichlet draws from the counts of ``z``."""
    dt, wt = counts(z, docs, mask, K, V)
    if sparse:
        _, k_theta, k_phi, k_next = jax.random.split(key, 4)
    else:
        k_theta, k_phi, k_next = jax.random.split(key, 3)
    g = jax.random.gamma(k_theta, alpha + dt)
    theta = _held_in(g / g.sum(axis=-1, keepdims=True), dtype)
    g = jax.random.gamma(k_phi, beta + wt)
    phi = _held_in(g / g.sum(axis=0, keepdims=True), dtype)
    return theta, phi, k_next


def _held_in(x, dtype):
    """``x`` rounded to ``dtype``'s precision and returned as float32.  An
    explicit rounding: XLA may drop a float32 -> bfloat16 -> float32 pair."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)


def _uniform(seed, c0, c1):
    """Threefry-2x32 of counters (c0, c1) under ``seed``: the top 24 bits
    of the first word, as a float32 in [0, 1)."""
    c0, c1 = jnp.broadcast_arrays(c0.astype(jnp.uint32), c1.astype(jnp.uint32))
    n = c0.size
    bits = threefry_2x32(seed, jnp.concatenate([c0.ravel(), c1.ravel()]))[:n]
    return ((bits >> 8).astype(jnp.int32).astype(jnp.float32) * np.float32(2**-24)).reshape(c0.shape)


def sparse_seed(key):
    kz = jax.random.split(key, 4)[0]
    return threefry_2x32(kz, jnp.array([SPARSE_TAG, 0], jnp.uint32))


def sparse_caps(zs, docs, mask, K: int, V: int, cap_min: int, cap_max: int) -> list:
    """The top-topics capacity of each sweep that starts from ``zs[i]``: a
    power of two in [cap_min, cap_max] covering the most topics any
    document holds, grown at once and shrunk only when that falls to a
    quarter of it (so that a capacity holds for many sweeps)."""
    caps, cap = [], None
    f = jax.jit(counts, static_argnames=("K", "V"))
    for z in zs:
        dt, _ = f(jnp.asarray(z), docs, mask, K=K, V=V)
        nnz = int(jnp.max(jnp.sum(dt > 0, axis=1)))
        want = max(cap_min, min(cap_max, 1 << (max(nnz, 1) - 1).bit_length()))
        if cap is None or want > cap or (nnz <= cap // 4 and want < cap):
            cap = want
        caps.append(min(cap, K))
    return caps


@functools.partial(jax.jit, static_argnames=("cap", "steps", "chunk", "dtype"))
def mh_draw(z, docs, mask, theta, phi, key, alpha, cap: int, steps: int, chunk: int,
            dtype=jnp.float32):
    """(M, maxN) topics after ``steps`` MH cycles from ``z``."""
    M, L = docs.shape
    K = theta.shape[1]
    V = phi.shape[0]
    theta, phi = _held_in(theta, dtype), _held_in(phi, dtype)
    cdf = jnp.cumsum(phi, axis=1)
    dt, _ = counts(z, docs, mask, K, V)
    cnt, ids = jax.lax.top_k(dt.astype(jnp.int32), cap)
    seed = sparse_seed(key)
    alpha = jnp.float32(alpha)
    ka = jnp.float32(K) * alpha
    nc = -(-M // chunk)
    pad = nc * chunk - M
    padded = lambda x: jnp.pad(x, ((0, pad), (0, 0))).reshape((nc, chunk) + x.shape[1:])
    d0 = jnp.arange(nc * chunk, dtype=jnp.uint32).reshape(nc, chunk)

    def one(x):
        zc, dc, mc, th, idc, cn, dd = x
        rows = dd[:, None] * jnp.uint32(L) + jnp.arange(L, dtype=jnp.uint32)
        cc = jnp.cumsum(cn, axis=1).astype(jnp.float32)
        mass = ka + cc[:, -1]
        take = lambda k: jnp.take_along_axis(th, k, axis=1)
        for st in range(steps):
            u = [_uniform(seed, rows, jnp.uint32(5 * st + j)) for j in range(5)]
            rc = cdf[dc]                                               # (C, L, K)
            t = u[0] * rc[..., -1]
            kp = jnp.minimum(jnp.sum((rc < t[..., None]).astype(jnp.int32), -1), K - 1)
            acc = (u[2] * take(zc) < take(kp)) & mc
            zc = jnp.where(acc, kp, zc)
            t = u[3] * mass[:, None]
            ku = jnp.minimum((t / alpha).astype(jnp.int32), K - 1)
            pos = jnp.sum((cc[:, None, :] <= (t - ka)[..., None]).astype(jnp.int32), -1)
            ks = jnp.take_along_axis(idc, jnp.minimum(pos, cap - 1), axis=1)
            kp = jnp.where(t < ka, ku, ks)
            n_of = lambda k: jnp.sum(jnp.where(idc[:, None, :] == k[..., None],
                                               cn[:, None, :], 0), -1).astype(jnp.float32)
            num = take(kp) * phi[dc, kp] * (alpha + n_of(zc))
            den = take(zc) * phi[dc, zc] * (alpha + n_of(kp))
            acc = (u[4] * den < num) & mc
            zc = jnp.where(acc, kp, zc)
        return zc

    zs = jax.lax.map(one, (padded(z), padded(docs), padded(mask), padded(theta),
                           padded(ids), padded(cnt), d0))
    return zs.reshape(nc * chunk, L)[:M]


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest absolute difference over the reference's largest entry."""
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))


def compare_sweep(before: dict, after: dict, docs, mask, alpha: float,
                  beta: float, chunk: int, dtype=jnp.float32, cap: int = 0,
                  mh_steps: int = 0) -> dict:
    """Follow one sweep from ``before`` (theta, phi, key) and compare what
    ``after`` holds.  The topics are compared with the reference's own
    draw; theta and phi with the reference's resample from the topics that
    ``after`` holds, so each stage is judged on its own inputs.  ``cap`` > 0
    follows the sparse MH sweep of ``mh_steps`` cycles with that top-topics
    capacity."""
    K = before["theta"].shape[1]
    V = before["phi"].shape[0]
    if cap:
        z_ref = np.asarray(mh_draw(jnp.asarray(before["z"]), docs, mask,
                                   jnp.asarray(before["theta"]), jnp.asarray(before["phi"]),
                                   jnp.asarray(before["key"]), alpha, cap=cap,
                                   steps=mh_steps, chunk=chunk, dtype=dtype))
    else:
        z_ref = np.asarray(draw(jnp.asarray(before["theta"]), jnp.asarray(before["phi"]),
                                jnp.asarray(before["key"]), docs, chunk=chunk,
                                dtype=dtype))
    z_bad = int(np.sum((z_ref != after["z"]) & np.asarray(mask)))
    theta, phi, k_next = resample(jnp.asarray(after["z"]), docs, mask,
                                  jnp.asarray(before["key"]), jnp.float32(alpha),
                                  jnp.float32(beta), K=K, V=V, dtype=dtype,
                                  sparse=bool(cap))
    return {
        "z_mismatch": z_bad,
        "theta_gap": rel_gap(after["theta"], np.asarray(theta)),
        "phi_gap": rel_gap(after["phi"], np.asarray(phi)),
        "key_mismatch": int(not np.array_equal(np.asarray(k_next), after["key"])),
    }
