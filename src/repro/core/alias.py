"""Walker/Vose alias method (paper §6 related work) as a JAX baseline.

Preprocessing is O(K) but inherently sequential (two worklists); we express
it with ``lax.while_loop`` over explicit array-backed stacks so it jits.
Draws are O(1): one uniform picks a column, a second decides
``k`` vs ``alias[k]``.  Useful when the same distribution is sampled many
times (Li et al. 2014 amortization); the paper's setting — each table used
*once* — is exactly where alias preprocessing cannot be amortized and the
butterfly approach wins.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class AliasTable(NamedTuple):
    prob: jnp.ndarray   # (K,) acceptance probability for the home column
    alias: jnp.ndarray  # (K,) fallback index


def build_alias_table(weights: jnp.ndarray) -> AliasTable:
    """Vose's O(K) construction for one distribution (1-D weights)."""
    K = weights.shape[0]
    w = weights.astype(jnp.float32)
    scaled = w * (K / jnp.sum(w))
    small_mask = scaled < 1.0
    order = jnp.argsort(small_mask)  # large entries first, then small
    n_small = jnp.sum(small_mask).astype(jnp.int32)
    n_large = K - n_small
    # stacks: indices of small entries and large entries
    small = jnp.where(small_mask, jnp.arange(K), -1)
    small = jnp.sort(jnp.where(small >= 0, small, K))[:K]
    large = jnp.where(~small_mask, jnp.arange(K), -1)
    large = jnp.sort(jnp.where(large >= 0, large, K))[:K]

    def cond(state):
        si, li = state[0], state[1]
        ns, nl = state[7], state[8]
        return jnp.logical_and(si < ns, li < nl)

    def body(state):
        si, li, scaled, prob, alias, small, large, n_small, n_large = state
        s = small[si]
        l = large[li]
        prob = prob.at[s].set(scaled[s])
        alias = alias.at[s].set(l)
        leftover = scaled[l] - (1.0 - scaled[s])
        scaled = scaled.at[l].set(leftover)
        is_small = leftover < 1.0
        # if the large entry became small, push it onto the small stack
        small = small.at[n_small].set(jnp.where(is_small, l, small[n_small]))
        n_small = n_small + jnp.where(is_small, 1, 0)
        li = li + jnp.where(is_small, 1, 0)
        si = si + 1
        return (si, li, scaled, prob, alias, small, large, n_small, n_large)

    prob = jnp.ones((K,), scaled.dtype)
    alias = jnp.arange(K, dtype=jnp.int32)
    small_pad = jnp.concatenate([small, jnp.zeros((K,), small.dtype)])[: 2 * K]
    state = (
        jnp.int32(0), jnp.int32(0), scaled, prob, alias,
        small_pad, large, n_small, n_large,
    )
    state = jax.lax.while_loop(cond, body, state)
    si, li, scaled, prob, alias, small_pad, large, n_small, n_large = state

    # drain: anything left on either stack gets prob 1 (numerical leftovers)
    def drain(stack, n, start, prob):
        def body(i, prob):
            idx = stack[i]
            return jnp.where(
                jnp.logical_and(i >= start, i < n),
                prob.at[jnp.clip(idx, 0, K - 1)].set(1.0),
                prob,
            )
        return jax.lax.fori_loop(0, stack.shape[0], body, prob)

    prob = drain(small_pad, n_small, si, prob)
    prob = drain(large, n_large, li, prob)
    return AliasTable(prob=prob.astype(jnp.float32), alias=alias)


build_alias_tables = jax.vmap(build_alias_table)  # over a (B, K) batch


def build_alias_tables_host(weights) -> AliasTable:
    """Row-vectorized host-side (numpy) Vose build over a (B, K) batch.

    The jittable ``build_alias_tables`` above is a vmapped
    ``lax.while_loop`` — XLA cannot keep its per-row stacks in place under
    vmap, so each of the ~K pair steps copies the whole (B, 2K) state:
    O(B*K^2) wall time (15s+ at vocab scale on CPU).  This twin runs the
    same Vose pairing with numpy fancy indexing, advancing every row one
    (small, large) pair per python iteration: O(B*K) total work, ~20x
    faster at (2048, 512), bit-agreeing draw semantics (leftover entries
    on either stack keep prob 1).  Weights must be concrete (it is a host
    build); the sparse-LDA sweep reaches it through the
    ``sampling.tables`` LRU cache so per-phi builds amortize across draw
    calls."""
    import numpy as np

    w = np.asarray(jax.device_get(weights), np.float64)
    if w.ndim != 2:
        raise ValueError(f"expected (B, K) weights, got shape {w.shape}")
    V, K = w.shape
    tot = w.sum(axis=1, keepdims=True)
    ok = (tot > 0).ravel()
    s = np.where(tot > 0, w * (K / np.where(tot > 0, tot, 1.0)), 1.0)
    prob = np.ones((V, K), np.float64)
    alias = np.tile(np.arange(K, dtype=np.int32), (V, 1))
    idx = np.tile(np.arange(K, dtype=np.int32), (V, 1))
    small_mask = s < 1.0
    # per-row worklists as stable argsorts: small entries first / large
    # entries first; the small stack is padded to 2K for demotions
    small_stack = np.argsort(
        np.where(small_mask, idx, K + idx), axis=1, kind="stable"
    ).astype(np.int32)
    large_stack = np.argsort(
        np.where(~small_mask, idx, K + idx), axis=1, kind="stable"
    ).astype(np.int32)
    n_small = small_mask.sum(axis=1).astype(np.int64)
    n_large = K - n_small
    small_stack = np.concatenate(
        [small_stack, np.zeros((V, K), np.int32)], axis=1
    )
    si = np.zeros(V, np.int64)
    li = np.zeros(V, np.int64)
    rows = np.arange(V)
    while True:
        active = (si < n_small) & (li < n_large) & ok
        if not active.any():
            break
        r = rows[active]
        sidx = small_stack[r, si[active]]
        lidx = large_stack[r, li[active]]
        ps = s[r, sidx]
        prob[r, sidx] = ps
        alias[r, sidx] = lidx
        leftover = s[r, lidx] - (1.0 - ps)
        s[r, lidx] = leftover
        demote = leftover < 1.0
        tails = n_small[active]
        small_stack[r[demote], tails[demote]] = lidx[demote]
        n_small[active] += demote.astype(np.int64)
        li[active] += demote.astype(np.int64)
        si[active] += 1
    return AliasTable(
        prob=jnp.asarray(prob.astype(np.float32)), alias=jnp.asarray(alias)
    )


def draw_alias(table: AliasTable, key: jax.Array, shape=()) -> jnp.ndarray:
    """O(1) draws from a single prebuilt table."""
    K = table.prob.shape[0]
    k_key, u_key = jax.random.split(key)
    k = jax.random.randint(k_key, shape, 0, K)
    u = jax.random.uniform(u_key, shape)
    return jnp.where(u < table.prob[k], k, table.alias[k]).astype(jnp.int32)


def draw_alias_batch(tables: AliasTable, key: jax.Array) -> jnp.ndarray:
    """One draw per row of a batch of tables (B, K)."""
    B, K = tables.prob.shape
    k_key, u_key = jax.random.split(key)
    k = jax.random.randint(k_key, (B,), 0, K)
    u = jax.random.uniform(u_key, (B,))
    home = jnp.take_along_axis(tables.prob, k[:, None], axis=1)[:, 0]
    ali = jnp.take_along_axis(tables.alias, k[:, None], axis=1)[:, 0]
    return jnp.where(u < home, k, ali).astype(jnp.int32)
