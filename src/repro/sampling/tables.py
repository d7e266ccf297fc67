"""Reusable distribution cache behind ``dist_key=``.

Alias and Fenwick state are pure functions of the weight matrix — when
the same distributions are drawn from repeatedly (a static unigram vocab
in decode, a fixed phi inside one LDA sweep), rebuilding them every call
wastes the dominant O(K) term.  Since the distribution-object redesign
this module is a thin wrapper over :mod:`repro.sampling`: it memoizes
built :class:`~repro.sampling.Categorical` pytrees (and, through the
legacy :meth:`TableCache.get_or_build`, their raw table leaves) for the
``dist_key=`` path of the ``sample_categorical`` shim.  The cached kinds
are exactly the ones whose state the shim reuses across calls.

Staleness: entries are keyed by a cheap **content digest** of the weights
(shape/dtype plus two O(BK) device-side reductions — see
:func:`content_digest`) in addition to the caller's ``dist_key``, so
silently changed weights can never serve a stale table: a changed matrix
digests differently and misses.  :meth:`invalidate` remains for eager
memory release; for explicit refresh semantics prefer holding a
``Categorical`` and calling ``dist.refreshed(new_weights)``.

Entries are LRU-evicted beyond ``max_entries``.  Tracer-safe: inside a
``jax.jit`` trace the weights are abstract (no digest exists), so caching
is silently skipped (the caller gets a freshly built — traced — table).
"""

from __future__ import annotations

import collections
import threading
import weakref
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

BUILDERS = ("alias", "alias_host", "alias_device", "fenwick")


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


@jax.jit
def _digest_reductions(w):
    """Two exact (integer, mod 2^32) order-sensitive checksums over the
    raw bytes of ``w``.

    Working on bitcast bytes with wraparound int32 arithmetic — never on
    float sums, where a small delta below the total's ulp (or at a zero
    of a weighting function) would be absorbed and digest identically —
    guarantees any single changed element changes at least one checksum.
    The position-weighted second sum catches permutations and paired
    swaps that preserve the plain sum."""
    raw = jnp.asarray(w)
    if raw.dtype == jnp.bool_:
        raw = raw.astype(jnp.int32)
    if not jnp.issubdtype(raw.dtype, jnp.integer):
        bts = jax.lax.bitcast_convert_type(raw, jnp.uint8)
    else:
        bts = raw
    iv = bts.astype(jnp.int32).ravel()
    pos = jnp.arange(iv.shape[0], dtype=jnp.int32)
    return jnp.sum(iv), jnp.sum(iv * (2 * pos + 1))


# per-array digest memo: jax arrays are immutable, so the digest of one
# *instance* never changes — memoizing by id() + a liveness weakref turns
# the repeated plan/draw lookups on a frozen distribution from two O(BK)
# device reductions + two scalar transfers each into a dict hit.  The
# weakref callback evicts on free so a recycled id can never alias a dead
# array's digest; the stored ref is also identity-checked on hit.
_DIGEST_MEMO: dict = {}
_DIGEST_LOCK = threading.Lock()


def _digest_memo_stats() -> int:
    with _DIGEST_LOCK:
        return len(_DIGEST_MEMO)


def content_digest(weights) -> Optional[str]:
    """Cheap content fingerprint of a weight matrix, or ``None`` for
    tracers (inside jit nothing concrete exists to digest).

    Shape/dtype plus two streaming byte-level checksums — one device pass
    and two scalar transfers, orders cheaper than hashing the full matrix
    host-side.  The checksums are exact integer arithmetic: a changed
    element always changes the digest (no float-rounding blind spots);
    only an adversarially constructed multi-element collision could slip
    through.  Memoized per array *instance* (arrays are immutable):
    repeated lookups on the same held matrix skip the reductions."""
    if _is_tracer(weights):
        return None
    wid = id(weights)
    with _DIGEST_LOCK:
        hit = _DIGEST_MEMO.get(wid)
        if hit is not None and hit[0]() is weights:
            return hit[1]
    s1, s2 = _digest_reductions(weights)
    digest = (
        f"{tuple(weights.shape)}|{weights.dtype}|{int(s1):#x}|{int(s2):#x}"
    )
    try:
        ref = weakref.ref(
            weights, lambda _r, k=wid: _DIGEST_MEMO.pop(k, None)
        )
    except TypeError:
        return digest  # not weakref-able (e.g. plain numpy scalar types)
    with _DIGEST_LOCK:
        _DIGEST_MEMO[wid] = (ref, digest)
    return digest


def _build(kind: str, weights, W: Optional[int]):
    """Legacy raw-table builder (kept for get_or_build compatibility)."""
    from repro.core import alias as _alias
    from repro.core import butterfly as _bfly

    W = W or _bfly.DEFAULT_W
    if kind == "alias":
        return _alias.build_alias_tables(weights)
    # host-side numpy Vose twin: O(BK) instead of the vmapped while_loop's
    # O(BK^2) — the sparse-LDA per-sweep phi tables go through this kind.
    # Tracer weights fall back to the jittable builder (no host build
    # exists inside a trace).
    if kind == "alias_host":
        if _is_tracer(weights):
            return _alias.build_alias_tables(weights)
        return _alias.build_alias_tables_host(weights)
    # on-device split-based build: a closed jaxpr, so it works for tracer
    # weights too — in-graph callers just build (no caching inside jit)
    if kind == "alias_device":
        from repro.kernels.alias_build import build_alias_tables_device

        return build_alias_tables_device(weights)
    # _prep is the uncached draw paths' dtype normalization + padding —
    # sharing it keeps cached tables bit-identical to per-call builds
    if kind == "fenwick":
        wp, _, _ = _bfly._prep(weights, W, group_pad=False)
        return _bfly.build_fenwick_table(wp, W)
    raise ValueError(f"unknown table kind {kind!r}; options: {BUILDERS}")


class TableCache:
    """LRU memo of built sampling state — raw tables (legacy
    :meth:`get_or_build`) and :class:`Categorical` pytrees
    (:meth:`get_or_build_dist`) — keyed by (dist_key, kind, W, content
    digest)."""

    def __init__(self, max_entries: int = 16):
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[Tuple, Any]" = (
            collections.OrderedDict()
        )
        self.hits = 0
        self.misses = 0

    def _lookup(self, key):
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
                return self._entries[key]
        return None

    def _store(self, key, value):
        with self._lock:
            self.misses += 1
            self._entries[key] = value
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
        return value

    def get_or_build(
        self,
        dist_key: str,
        kind: str,
        weights,
        W: Optional[int] = None,
    ):
        """Return the cached raw table for ``dist_key`` or build and cache.

        The weights' content digest is part of the internal key, so a
        stale ``dist_key`` reused at a different shape — or with silently
        changed values — misses and rebuilds instead of serving a stale
        table."""
        digest = content_digest(weights)
        if digest is None:
            return _build(kind, weights, W)  # inside jit: no caching
        key = ("raw", str(dist_key), kind, W, digest)
        hit = self._lookup(key)
        if hit is not None:
            return hit
        return self._store(key, _build(kind, weights, W))

    def get_or_build_dist(self, dist_key: str, plan, weights):
        """Return the cached :class:`Categorical` for ``dist_key`` under
        ``plan`` (a ``repro.sampling.SamplerPlan``), building on miss.

        Same digest-keyed staleness contract as :meth:`get_or_build`."""
        digest = content_digest(weights)
        if digest is None:
            return plan.build(weights)  # inside jit: no caching
        key = ("dist", str(dist_key), plan.method, plan.W, digest)
        hit = self._lookup(key)
        if hit is not None:
            return hit
        return self._store(key, plan.build(weights))

    def invalidate(self, dist_key: str) -> int:
        """Drop every entry for ``dist_key`` (all kinds/digests); returns
        how many were removed."""
        dist_key = str(dist_key)
        with self._lock:
            doomed = [k for k in self._entries if k[1] == dist_key]
            for k in doomed:
                del self._entries[k]
        return len(doomed)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
            }


_GLOBAL: Optional[TableCache] = None


def get_table_cache() -> TableCache:
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = TableCache()
    return _GLOBAL


def reset_table_cache() -> None:
    global _GLOBAL
    _GLOBAL = None
