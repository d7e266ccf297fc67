"""Sparsity-aware LDA Gibbs sweep: MH-alias proposals over sparse counts.

The dense z-draw (``gibbs._scan_draw``) pays O(K) per token however few
topics a document or word actually touches.  This module drives the
per-token cost to O(cap + log K) — sublinear in K — with the
WarpLDA/EZLDA construction adapted to the uncollapsed sampler
(DESIGN.md §10):

* **Three-branch decomposition** (EZLDA).  The doc-side proposal mass
  ``alpha + n_dk`` splits into a *smoothing* branch (total ``K * alpha``,
  drawn uniformly in O(1)) and a *doc-sparse* branch (total
  ``sum_k n_dk``, drawn by a partial-sums walk over only the K_d live
  topics).  The dense *word-sparse* term ``phi[w, :]`` becomes the word
  proposal, drawn O(1) from a per-word alias table (or O(log K) from
  per-word partial sums).
* **Fixed-width sparse doc-topic counts.**  Per-doc (topic-id, count)
  lists of static width ``cap`` (a power of two).  ``cap`` is bucketed —
  grown immediately when a doc's nonzero count outgrows it, shrunk only
  on 4x slack — so the whole sweep stays one compiled ``lax.scan`` per
  capacity bucket with zero retraces inside a bucket.
* **MH-within-Gibbs z-draw** (WarpLDA).  Each token alternates two
  Metropolis-Hastings proposals targeting ``p(k) ~ theta[d,k]*phi[w,k]``:

    - *word proposal*: ``k' ~ q_w(k) = phi[w,k]`` via the alias table;
      acceptance ratio collapses to ``theta[d,k']/theta[d,k]``.
    - *doc proposal*: ``k' ~ q_d(k) = (alpha + n~_dk) / mass`` with
      ``mass = K*alpha + sum(n~_d)`` over the *retained* (possibly
      truncated) count list; acceptance
      ``(theta'phi'(alpha+n~_k)) / (theta phi (alpha+n~_k'))``.

  Because the proposal mass is the retained mass — not the true token
  count — truncation at ``cap`` keeps the kernel *exact*: dropped topics
  stay reachable through the smoothing branch and the acceptance ratio
  uses the same truncated ``n~`` the proposal density does.  Capacity
  regrowth is a mixing-quality knob, never a correctness requirement.

Word-proposal tables are built once per sweep from the *concrete* phi at
the sweep boundary and reused across every token:

* ``word_proposal="alias"`` — exact Vose tables via the row-vectorized
  host builder (``core.alias.build_alias_tables_host``), memoized in the
  ``sampling.tables`` LRU cache keyed by phi's content digest, so
  repeated draws against a frozen phi never rebuild.  O(1) per proposal.
* ``word_proposal="cdf"`` — per-word inclusive partial sums (one cumsum,
  O(VK) build, always cheap) walked by a butterfly-style dyadic descent:
  O(log K) per proposal with scalar gathers only.
* ``word_proposal="alias_device"`` — the split-based *device* alias build
  (``kernels.alias_build``): same O(1) draw as ``alias`` but the build is
  a closed jaxpr of data-parallel primitives, so training sweeps that
  resample phi every sweep rebuild in-graph at parallel-sort cost instead
  of the host's serial Vose walk (and the distributed sweep can build it
  inside ``shard_map``, which the host LRU path never could).

The sweep never materializes a (tokens, K) tensor: every per-token
quantity is a scalar gather or a (chunk, L, cap) compare
(``tests/test_lda_sparse.py`` gates the jaxpr).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import rng as _rng
from repro.lda.corpus import Corpus
from repro.lda.gibbs import LDAState, _update_phi, _update_theta

WORD_PROPOSALS = ("alias", "alias_device", "cdf")

DEFAULT_CAP_MIN = 8
DEFAULT_CAP_MAX = 64


class SparseDocTopics(NamedTuple):
    """Fixed-width sparse doc-topic counts: per-doc top-``cap`` topics.

    Slots beyond a doc's nonzero count carry ``cnt == 0`` (their ids are
    arbitrary); when a doc's support exceeds ``cap`` the *largest* counts
    are retained (see the truncation-exactness note in the module doc)."""

    ids: jnp.ndarray  # (M, cap) int32 topic ids
    cnt: jnp.ndarray  # (M, cap) int32 counts


@functools.partial(jax.jit, static_argnames=("cap",))
def sparse_counts(doc_topic: jnp.ndarray, cap: int) -> SparseDocTopics:
    """Top-``cap`` sparse view of dense (M, K) doc-topic counts."""
    cap = min(cap, doc_topic.shape[-1])
    cnt, ids = jax.lax.top_k(doc_topic.astype(jnp.int32), cap)
    return SparseDocTopics(ids=ids.astype(jnp.int32), cnt=cnt)


@functools.partial(jax.jit, static_argnames=("K", "V"))
def _counts_scatter(z, docs, mask, K: int, V: int):
    """Scatter-based (doc_topic, word_topic) counts.

    The dense sweep's ``_counts`` builds a (M, N, K) one-hot; at sparse-
    LDA topic counts that intermediate dwarfs the draw itself, so the
    sparse sweep counts by scatter-add: masked positions land in a
    throwaway K-th bucket that is sliced off."""
    M = z.shape[0]
    zm = jnp.where(mask, z, K)
    ones = jnp.ones(z.shape, jnp.float32)
    doc_topic = (
        jnp.zeros((M, K + 1), jnp.float32)
        .at[jnp.arange(M, dtype=jnp.int32)[:, None], zm]
        .add(ones)[:, :K]
    )
    word_topic = (
        jnp.zeros((V, K + 1), jnp.float32)
        .at[docs, zm]
        .add(ones)[:, :K]
    )
    return doc_topic, word_topic


@jax.jit
def _nnz_max(doc_topic) -> jnp.ndarray:
    return jnp.max(jnp.sum((doc_topic > 0).astype(jnp.int32), axis=1))


@jax.jit
def _phi_cdf(phi) -> jnp.ndarray:
    """(V, K) inclusive per-word partial sums of phi rows (unnormalized:
    the draw rescales by the row total, so phi rows needn't sum to 1)."""
    return jnp.cumsum(phi.astype(jnp.float32), axis=1)


def pow2_capacity(
    nnz: int, cap_min: int = DEFAULT_CAP_MIN, cap_max: int = DEFAULT_CAP_MAX
) -> int:
    """Power-of-two capacity bucket covering ``nnz``, clamped to
    [cap_min, cap_max] (the clamp is safe: truncation keeps MH exact)."""
    n = max(int(nnz), 1)
    want = 1 << (n - 1).bit_length()
    return max(cap_min, min(cap_max, want))


@dataclasses.dataclass
class SparseSweepCache:
    """Caller-held mutable state the sparse sweep carries across sweeps
    (mirrors the ``dists=`` pattern of the dense path): the current
    capacity bucket, the sparse counts entering the next sweep, and the
    bucket/acceptance history the tests and benches read."""

    cap_min: int = DEFAULT_CAP_MIN
    cap_max: int = DEFAULT_CAP_MAX
    cap: Optional[int] = None
    counts: Optional[SparseDocTopics] = None
    nnz_max: int = 0
    caps_history: List[int] = dataclasses.field(default_factory=list)
    last_stats: Optional[Dict[str, float]] = None

    def update_capacity(self, nnz_max: int) -> int:
        """Hysteretic pow2 bucketing: grow immediately when the observed
        max support outgrows the bucket; shrink only when it falls to a
        quarter of it.  One retrace per bucket change, none inside."""
        self.nnz_max = int(nnz_max)
        want = pow2_capacity(self.nnz_max, self.cap_min, self.cap_max)
        if self.cap is None:
            self.cap = want
        elif want > self.cap:
            self.cap = want
        elif self.nnz_max <= self.cap // 4 and want < self.cap:
            self.cap = want
        if not self.caps_history or self.caps_history[-1] != self.cap:
            self.caps_history.append(self.cap)
        return self.cap


# ---------------------------------------------------------------------------
# The MH sweep kernel
# ---------------------------------------------------------------------------


def _ceil_log2(n: int) -> int:
    return max(1, (int(n) - 1).bit_length())


def _mh_sweep(
    z, docs, mask, theta, phi, ids, cnt, tbl_a, tbl_b, seed, row0, alpha,
    *, steps: int, cap: int, mode: str, chunk: int,
):
    """``steps`` MH cycles over every token; one ``lax.scan`` over doc
    chunks.  Returns (z, word_accepts, doc_accepts, proposals).

    Randomness is the counter RNG: the uniform for (token, use) is a pure
    function of (seed, global token id, 5*step + use), where the global
    token id is ``(row0 + doc_index) * L + position`` — shard- and
    chunk-layout invariant, so distributed and streaming sweeps draw
    bit-identically to the single-device sweep."""
    M, L = docs.shape
    K = theta.shape[-1]
    Kf = jnp.float32(K)
    alpha = jnp.float32(alpha)
    chunk = min(chunk, M) if M else chunk
    nc = max(1, -(-M // chunk))
    pad = nc * chunk - M
    if pad:
        z = jnp.pad(z, ((0, pad), (0, 0)))
        docs = jnp.pad(docs, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
        theta = jnp.pad(theta, ((0, pad), (0, 0)), constant_values=1.0)
        ids = jnp.pad(ids, ((0, pad), (0, 0)))
        cnt = jnp.pad(cnt, ((0, pad), (0, 0)))
    cc = jnp.cumsum(cnt, axis=1).astype(jnp.float32)       # (M', cap)
    S = cc[:, -1]                                          # retained mass
    dbase = jnp.asarray(row0, jnp.uint32) + jnp.arange(
        nc * chunk, dtype=jnp.uint32
    )
    flat_phi = phi.reshape(-1)
    flat_a = tbl_a.reshape(-1)
    flat_b = tbl_b.reshape(-1)
    if mode == "cdf":
        row_tot = tbl_a[:, -1]                             # (V,) row totals
    span0 = 1 << _ceil_log2(K)

    def word_propose(wc, u0, u1):
        if mode in ("alias", "alias_device"):
            kr = jnp.minimum((u0 * Kf).astype(jnp.int32), K - 1)
            pw = flat_a[wc * K + kr]
            ka = flat_b[wc * K + kr].astype(jnp.int32)
            return jnp.where(u1 < pw, kr, ka)
        # butterfly-style dyadic descent on the word's partial sums:
        # branchless lower_bound, log2(K) scalar gathers, no (B, K) row
        t = u0 * row_tot[wc]
        base = jnp.zeros_like(wc)
        span = span0
        while span > 1:
            span //= 2
            cand = base + span - 1
            val = flat_a[wc * K + jnp.minimum(cand, K - 1)]
            base = base + jnp.where((cand < K) & (val < t), span, 0)
        return jnp.minimum(base, K - 1)

    def body(carry, xs):
        zc, dc, mc, thc, idsc, cntc, ccc, Sc, dbc = xs
        wa, da = carry
        rows = dbc[:, None] * jnp.uint32(L) + jnp.arange(L, dtype=jnp.uint32)
        mass = Kf * alpha + Sc                             # (C,)

        def cycle(s, st):
            zc, wa, da = st
            u = [
                _rng.uniform(seed, rows, jnp.uint32(5) * s + jnp.uint32(j))
                for j in range(5)
            ]
            # ---- word proposal: q ~ phi[w, :], accept on theta ratio
            kp = word_propose(dc, u[0], u[1])
            thz = jnp.take_along_axis(thc, zc, axis=1)
            thp = jnp.take_along_axis(thc, kp, axis=1)
            acc = (u[2] * thz < thp) & mc
            zc = jnp.where(acc, kp, zc)
            wa = wa + jnp.sum(acc.astype(jnp.int32))
            # ---- doc proposal: smoothing + doc-sparse branches
            t = u[3] * mass[:, None]                       # (C, L)
            smooth = t < Kf * alpha
            ku = jnp.minimum((t / alpha).astype(jnp.int32), K - 1)
            pos = jnp.sum(
                (ccc[:, None, :] <= (t - Kf * alpha)[..., None]).astype(
                    jnp.int32
                ),
                axis=2,
            )
            pos = jnp.minimum(pos, cap - 1)
            ks = jnp.take_along_axis(idsc, pos, axis=1)
            kp = jnp.where(smooth, ku, ks)
            # retained counts at current/proposed topic (q_d's density)
            ncur = jnp.sum(
                jnp.where(idsc[:, None, :] == zc[..., None], cntc[:, None, :], 0),
                axis=2,
            ).astype(jnp.float32)
            nprop = jnp.sum(
                jnp.where(idsc[:, None, :] == kp[..., None], cntc[:, None, :], 0),
                axis=2,
            ).astype(jnp.float32)
            thz = jnp.take_along_axis(thc, zc, axis=1)
            thp = jnp.take_along_axis(thc, kp, axis=1)
            phz = flat_phi[dc * K + zc]
            php = flat_phi[dc * K + kp]
            num = thp * php * (alpha + ncur)
            den = thz * phz * (alpha + nprop)
            acc = (u[4] * den < num) & mc
            zc = jnp.where(acc, kp, zc)
            da = da + jnp.sum(acc.astype(jnp.int32))
            return (zc, wa, da)

        # few cycles unroll (XLA fuses across them); many cycles — the
        # statistical-equivalence tests run dozens — roll into a
        # fori_loop so graph size and compile time stay flat
        if steps <= 4:
            st = (zc, wa, da)
            for s in range(steps):
                st = cycle(jnp.uint32(s), st)
            zc, wa, da = st
        else:
            zc, wa, da = jax.lax.fori_loop(
                0, steps,
                lambda s, st: cycle(jnp.uint32(s), st),
                (zc, wa, da),
            )
        return (wa, da), zc

    xs = (
        z.reshape(nc, chunk, L),
        docs.reshape(nc, chunk, L),
        (mask > 0).reshape(nc, chunk, L),
        theta.reshape(nc, chunk, K),
        ids.reshape(nc, chunk, cap),
        cnt.reshape(nc, chunk, cap),
        cc.reshape(nc, chunk, cap),
        S.reshape(nc, chunk),
        dbase.reshape(nc, chunk),
    )
    (wa, da), zs = jax.lax.scan(
        body, (jnp.int32(0), jnp.int32(0)), xs
    )
    props = jnp.sum((mask > 0).astype(jnp.int32)) * steps
    return zs.reshape(nc * chunk, L)[:M], wa, da, props


_JIT_CACHE: Dict[Tuple, Callable] = {}


def _mh_sweep_jit(steps: int, cap: int, mode: str, chunk: int) -> Callable:
    key = ("mh", steps, cap, mode, chunk)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(
            functools.partial(
                _mh_sweep, steps=steps, cap=cap, mode=mode, chunk=chunk
            )
        )
        _JIT_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Word-proposal tables
# ---------------------------------------------------------------------------


def word_proposal_tables(
    phi, mode: str, dist_key: str = "lda_sparse_phi"
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(tbl_a, tbl_b) for the word proposal, built once per (phi, mode).

    ``alias``: exact Vose (prob, alias) via the *host* builder through
    the ``sampling.tables`` LRU cache keyed by phi's content digest — a frozen
    phi (posterior draws, repeated ``draw_z_sparse``) never rebuilds.
    ``alias_device``: the split-based device build — a closed jaxpr, so
    it works on tracer phi (inside jit / shard_map) and rebuilds a
    per-sweep phi at parallel-sort cost; concrete phi goes through the
    same digest-keyed LRU so frozen-phi callers still skip the build.
    ``cdf``: per-word inclusive partial sums (tbl_b is a dummy scalar —
    static shapes keep the jit cache small)."""
    if mode in ("alias", "alias_device"):
        from repro.sampling.tables import get_table_cache

        kind = "alias_host" if mode == "alias" else "alias_device"
        table = get_table_cache().get_or_build(dist_key, kind, phi)
        return table.prob, table.alias
    if mode == "cdf":
        return _phi_cdf(phi), jnp.zeros((1, 1), jnp.int32)
    raise ValueError(
        f"unknown word_proposal {mode!r}; options: {WORD_PROPOSALS}"
    )


# ---------------------------------------------------------------------------
# Public sweep / draw entry points
# ---------------------------------------------------------------------------


def _host_int(x) -> int:
    """A device scalar read back to the host: an ``lda.sync`` span, in
    which the host waits for every program queued before it."""
    with obs.span("lda.sync"):
        return int(x)


def draw_z_sparse(
    state: LDAState,
    docs,
    mask,
    mh_steps: int = 2,
    word_proposal: str = "alias",
    alpha: float = 0.1,
    cache: Optional[SparseSweepCache] = None,
    chunk: int = 256,
    row0: int = 0,
    return_stats: bool = False,
):
    """Standalone sparse z-draw (``mh_steps`` MH cycles from ``state.z``).

    Unlike the dense ``draw_z`` — an exact per-token draw — this advances
    an MH chain whose stationary per-token law is the exact conditional;
    more steps converge the per-call marginals (the statistical-
    equivalence test runs dozens)."""
    docs = jnp.asarray(docs)
    mask = jnp.asarray(mask)
    K = state.theta.shape[-1]
    V = state.phi.shape[0]
    if cache is None:
        cache = SparseSweepCache()
    if cache.counts is None or cache.cap is None:
        doc_topic, _ = _counts_scatter(docs=docs, mask=mask, z=state.z, K=K, V=V)
        cache.update_capacity(_host_int(_nnz_max(doc_topic)))
        cache.counts = sparse_counts(doc_topic, min(cache.cap, K))
    tbl_a, tbl_b = word_proposal_tables(state.phi, word_proposal)
    seed = _rng.fold(_rng.seed_from_key(state.key), _rng.TAG_SPARSE_MH)
    z, wa, da, props = _mh_sweep_jit(
        mh_steps, min(cache.cap, K), word_proposal, chunk
    )(
        state.z, docs, mask, state.theta, state.phi,
        cache.counts.ids, cache.counts.cnt, tbl_a, tbl_b, seed,
        jnp.uint32(row0), jnp.float32(alpha),
    )
    if return_stats:
        return z, _stats_dict(wa, da, props)
    return z


def _stats_dict(wa, da, props) -> Dict[str, float]:
    p = max(_host_int(props), 1)
    return {
        "word_accept_rate": float(int(wa) / p),
        "doc_accept_rate": float(int(da) / p),
        "proposals_per_kind": p,
    }


def gibbs_step_sparse(
    state: LDAState,
    corpus: Corpus,
    alpha: float = 0.1,
    beta: float = 0.05,
    mh_steps: int = 2,
    word_proposal: str = "cdf",
    cache: Optional[SparseSweepCache] = None,
    chunk: int = 256,
    row0: int = 0,
) -> LDAState:
    """One full sparse Gibbs sweep — same ``LDAState`` in/out as the
    dense ``gibbs_step``: MH z-draw, scatter counts, Dirichlet theta/phi
    resample.  Pass the same ``cache`` every sweep to carry the sparse
    counts and capacity bucket across sweeps (a throwaway cache rebuilds
    them from ``state.z``, which costs one dense count pass).

    ``word_proposal`` defaults to ``"cdf"`` here: training sweeps change
    phi every step, so the O(VK) partial-sums build (one cumsum) beats a
    per-sweep *serial* alias construction; ``"alias"`` remains the right
    choice for frozen-phi posterior draws via :func:`draw_z_sparse`.
    ``"alias_device"`` rebuilds alias tables in-graph at parallel-sort
    cost — O(1) word proposals even though phi changes every sweep.

    The host call is an ``lda.sweep`` span (``repro.obs``) as in the dense
    ``gibbs_step``; its ``lda.sync`` children are the host readbacks that
    size the next sweep's capacity bucket."""
    K = state.theta.shape[-1]
    V = state.phi.shape[0]
    if cache is None:
        cache = SparseSweepCache()
    with obs.span("lda.sweep", index=obs.count("lda.sweeps") - 1):
        with obs.span("lda.upload"):
            docs = jnp.asarray(corpus.docs)
            mask = jnp.asarray(corpus.mask)
        if cache.counts is None or cache.cap is None:
            with obs.span("lda.dispatch"):
                doc_topic, _ = _counts_scatter(
                    docs=docs, mask=mask, z=state.z, K=K, V=V
                )
                nnz = _nnz_max(doc_topic)
            cache.update_capacity(_host_int(nnz))
            with obs.span("lda.dispatch"):
                cache.counts = sparse_counts(doc_topic, min(cache.cap, K))
        with obs.span("lda.dispatch"):
            tbl_a, tbl_b = word_proposal_tables(state.phi, word_proposal)
            kz, k_theta, k_phi, k_next = jax.random.split(state.key, 4)
            seed = _rng.fold(_rng.seed_from_key(kz), _rng.TAG_SPARSE_MH)
            z, wa, da, props = _mh_sweep_jit(
                mh_steps, min(cache.cap, K), word_proposal, chunk
            )(
                state.z, docs, mask, state.theta, state.phi,
                cache.counts.ids, cache.counts.cnt, tbl_a, tbl_b, seed,
                jnp.uint32(row0), jnp.float32(alpha),
            )
            doc_topic, word_topic = _counts_scatter(z, docs, mask, K, V)
            theta = _update_theta(k_theta, doc_topic, alpha)
            phi = _update_phi(k_phi, word_topic, beta)
            nnz = _nnz_max(doc_topic)
        # next sweep's proposal counts (and the capacity bucket they live in)
        cache.update_capacity(_host_int(nnz))
        with obs.span("lda.dispatch"):
            cache.counts = sparse_counts(doc_topic, min(cache.cap, K))
        cache.last_stats = _stats_dict(wa, da, props)
    return LDAState(theta=theta, phi=phi, z=z, key=k_next, step=state.step + 1)


# ---------------------------------------------------------------------------
# Streaming million-doc sweep
# ---------------------------------------------------------------------------


class StreamingSparseLDA:
    """Host-streamed sparse Gibbs: corpus shards flow through the sweep
    one at a time, so only (phi, one shard, the (V, K) count accumulator)
    ever reside on device — a million-document corpus trains on a box
    whose device memory holds none of it.

    Per sweep, per shard: regenerate theta from the shard's current
    counts (theta is a Dirichlet resample every sweep anyway, so it needs
    no persistent storage), run the MH sweep with *global* doc offsets
    (counter-RNG draws are shard-layout invariant), accumulate the
    word-topic counts, and store back only the packed z tokens.  Phi is
    resampled once at the sweep end from the accumulated counts — the
    same single-synchronization schedule as distributed AD-LDA, with the
    psum replaced by host-sequential accumulation.

    ``source`` must expose ``num_shards``, ``vocab_size``, and
    ``shard(i) -> (docs, mask)`` numpy arrays of a fixed width L
    (see ``corpus.zipf_shard_source``)."""

    def __init__(
        self,
        key,
        source,
        K: int,
        alpha: float = 0.1,
        beta: float = 0.05,
        mh_steps: int = 1,
        word_proposal: str = "cdf",
        cap: int = 32,
        chunk: int = 512,
    ):
        self.source = source
        self.K = int(K)
        self.V = int(source.vocab_size)
        self.alpha = float(alpha)
        self.beta = float(beta)
        self.mh_steps = int(mh_steps)
        self.word_proposal = word_proposal
        self.cap = int(cap)
        self.chunk = int(chunk)
        k_phi, self.key = jax.random.split(jax.random.PRNGKey(0) if key is None else key)
        self.phi = jax.random.dirichlet(
            k_phi, jnp.ones((self.V,)), shape=(self.K,)
        ).T
        self._z_packed: List[Optional[np.ndarray]] = [None] * source.num_shards
        self.sweeps_done = 0
        self.last_ll = None

    def _shard_z(self, i: int, mask: np.ndarray, key) -> jnp.ndarray:
        z = np.zeros(mask.shape, np.int32)
        packed = self._z_packed[i]
        if packed is None:
            s0, s1 = np.asarray(_rng.seed_from_key(key), np.uint64)
            rng = np.random.default_rng(((int(s0) << 32) | int(s1)) + i)
            z[mask] = rng.integers(0, self.K, size=int(mask.sum()))
        else:
            z[mask] = packed
        return jnp.asarray(z)

    def sweep(self) -> Dict[str, float]:
        """One full pass over every shard; returns throughput stats."""
        t0 = time.perf_counter()
        kz, k_theta, k_phi, k_init, self.key = jax.random.split(self.key, 5)
        tbl_a, tbl_b = word_proposal_tables(self.phi, self.word_proposal)
        seed = _rng.fold(_rng.seed_from_key(kz), _rng.TAG_SPARSE_MH)
        wt = jnp.zeros((self.V, self.K), jnp.float32)
        ll = jnp.float32(0.0)
        tokens = 0
        wa = da = props = 0
        for i in range(self.source.num_shards):
            docs_np, mask_np = self.source.shard(i)
            docs = jnp.asarray(docs_np)
            mask = jnp.asarray(mask_np)
            z = self._shard_z(i, np.asarray(mask_np, bool), k_init)
            doc_topic, _ = _counts_scatter(z, docs, mask, self.K, self.V)
            theta = _update_theta(
                jax.random.fold_in(k_theta, i), doc_topic, self.alpha
            )
            sp = sparse_counts(doc_topic, self.cap)
            row0 = i * docs.shape[0]
            z, a_w, a_d, p = _mh_sweep_jit(
                self.mh_steps, min(self.cap, self.K), self.word_proposal,
                self.chunk,
            )(
                z, docs, mask, theta, self.phi, sp.ids, sp.cnt,
                tbl_a, tbl_b, seed, jnp.uint32(row0), jnp.float32(self.alpha),
            )
            doc_topic, word_topic = _counts_scatter(
                z, docs, mask, self.K, self.V
            )
            wt = wt + word_topic
            theta2 = _update_theta(
                jax.random.fold_in(k_theta, self.source.num_shards + i),
                doc_topic, self.alpha,
            )
            ll = ll + _shard_ll(theta2, self.phi, docs, mask)
            mask_b = np.asarray(mask_np, bool)
            self._z_packed[i] = np.asarray(z)[mask_b].astype(np.int32)
            tokens += int(mask_b.sum())
            wa += int(a_w); da += int(a_d); props += int(p)
        self.phi = _update_phi(k_phi, wt, self.beta)
        jax.block_until_ready(self.phi)
        dt = time.perf_counter() - t0
        self.sweeps_done += 1
        self.last_ll = float(ll)
        return {
            "tokens": tokens,
            "seconds": dt,
            "tokens_per_sec": tokens / max(dt, 1e-9),
            "perplexity": float(np.exp(-self.last_ll / max(tokens, 1))),
            "word_accept_rate": wa / max(props, 1),
            "doc_accept_rate": da / max(props, 1),
        }


@jax.jit
def _shard_ll(theta, phi, docs, mask):
    p = jnp.einsum("mk,mnk->mn", theta, phi[docs])
    return jnp.where(mask > 0, jnp.log(jnp.maximum(p, 1e-30)), 0.0).sum()
