"""Counter-based draw RNG: one scalar key, zero uniform buffers.

Every draw path used to receive its randomness as a host-fed ``(B,)``
uniform vector produced by a ``jax.random.split`` chain — per draw call
one key split, one ``uniform`` dispatch, one (B,) buffer that pass B then
re-reads as a kernel operand.  This module replaces that with a
*counter-based* generator (Threefry-2x32, the same cipher behind JAX's
default PRNG): the uniform for (row, draw) is a pure function of

    u = uniform(seed, counter0=global_row, counter1=draw_index)

where ``seed`` is a single (2,) uint32 pair derived once from a PRNG key.
Consequences the sharded sampler is built on (DESIGN.md §5):

* **No key-split chain.**  Multi-draw decode and multi-sweep Gibbs need
  no per-draw keys — the draw index is just the second counter word, so
  launch count is independent of S.
* **Device-count invariance.**  Counters are *global* row ids; a shard
  computes its rows from its mesh position, so 1/2/8-device meshes
  produce bit-identical draws for the same key
  (``tests/test_sharded_sampler.py`` pins this).
* **In-kernel generation.**  The cipher is ~40 uint32 add/xor/shift ops
  on vectors — the same code runs in XLA, under Pallas interpret mode,
  and compiled inside a TPU kernel body, so the fused draw kernel can
  generate its own uniforms and drop the (B,) operand entirely.

TPU hardware PRNG (``pltpu.prng_seed`` / ``prng_random_bits``) is
available as an opt-in fast path for the fused kernel (``hw_rng=True``);
it is per-tile-seeded and therefore still deterministic for a fixed tile
layout, but its bit-stream differs from the Threefry twin, so the
portable cipher stays the default on every backend.

Stream separation: callers fold a domain tag (and, for per-draw streams,
a draw index) into the seed first via :func:`fold` — the u-driven draw,
Gumbel noise, and the two alias coordinates each get an independent
stream from one key.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# Threefry-2x32 constants (Salmon et al. 2011; identical to JAX's PRNG).
_KS_PARITY = np.uint32(0x1BD11BDA)
_ROTS = ((13, 15, 26, 6), (17, 29, 16, 24))

# domain tags: independent streams derived from one seed via fold()
TAG_U = 1          # u-driven variants' per-(row, draw) uniform
TAG_GUMBEL = 2     # per-(row, category) Gumbel noise
TAG_ALIAS_J = 3    # alias draw: column pick
TAG_ALIAS_A = 4    # alias draw: accept coordinate
TAG_SPARSE_MH = 5  # sparse LDA MH-alias sweep: per-(token, use) uniforms


def _rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """The Threefry-2x32 block cipher (20 rounds).

    All inputs are uint32 scalars/arrays (broadcast together); returns
    the two output words.  Pure elementwise uint32 ops, so the same code
    traces in XLA, runs under Pallas interpret mode, and compiles in a
    TPU kernel body.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(x0).astype(jnp.uint32)
    x1 = jnp.asarray(x1).astype(jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ _KS_PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def seed_from_key(key) -> jnp.ndarray:
    """(2,) uint32 seed pair from a JAX PRNG key (typed or raw uint32)."""
    arr = jnp.asarray(key)
    if not jnp.issubdtype(arr.dtype, jnp.integer):  # typed key array
        arr = jax.random.key_data(key)
    arr = arr.reshape(-1).astype(jnp.uint32)
    if arr.shape[0] == 1:
        arr = jnp.concatenate([jnp.zeros((1,), jnp.uint32), arr])
    return arr[-2:]


def fold(seed: jnp.ndarray, a, b=0) -> jnp.ndarray:
    """Derive an independent (2,) seed from (seed, a, b) — the chain-free
    replacement for ``jax.random.fold_in``; a and b may be traced."""
    s0, s1 = threefry2x32(seed[0], seed[1], a, b)
    return jnp.stack([s0, s1])


def bits_to_uniform(bits) -> jnp.ndarray:
    """uint32 bits -> float32 uniforms in [0, 1) (top 24 bits)."""
    # via int32: the top 24 bits fit, and Mosaic has no uint32 -> f32 cast
    top = (jnp.asarray(bits, jnp.uint32) >> np.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * np.float32(2**-24)


def uniform(seed: jnp.ndarray, counter0, counter1=0) -> jnp.ndarray:
    """Uniforms in [0, 1), one per broadcast element of the counters.

    ``counter0`` is conventionally the *global* row id, ``counter1`` the
    draw index (or category column for matrix-shaped noise).
    """
    c0 = jnp.asarray(counter0).astype(jnp.uint32)
    c1 = jnp.broadcast_to(
        jnp.asarray(counter1).astype(jnp.uint32), jnp.broadcast_shapes(
            jnp.shape(counter0), jnp.shape(counter1)
        )
    )
    b0, _ = threefry2x32(seed[0], seed[1], jnp.broadcast_to(c0, c1.shape), c1)
    return bits_to_uniform(b0)


def row_uniforms(seed: jnp.ndarray, row0, n: int, draw=0) -> jnp.ndarray:
    """(n,) uniforms for global rows [row0, row0 + n) at one draw index."""
    rows = jnp.asarray(row0, jnp.uint32) + jnp.arange(n, dtype=jnp.uint32)
    return uniform(seed, rows, draw)


def multi_row_uniforms(seed: jnp.ndarray, row0, n: int, S: int) -> jnp.ndarray:
    """(S, n) uniforms: draw s of global row r is counter (r, s) — the
    S-independent multi-draw form (no key per draw, no buffer per draw)."""
    rows = jnp.asarray(row0, jnp.uint32) + jnp.arange(n, dtype=jnp.uint32)
    return uniform(seed, rows[None, :], jnp.arange(S, dtype=jnp.uint32)[:, None])
