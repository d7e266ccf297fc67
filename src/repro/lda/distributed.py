"""Distributed LDA: documents shard over the data axes, phi replicates.

The sweep is a ``shard_map`` over the mesh's data axes — the classic
data-parallel AD-LDA layout (Newman et al.), made explicit instead of
left to GSPMD:

* **z-draw** — each shard draws its own word positions through the
  ``repro.sampling`` plan/Categorical factored path (``lda_kernel`` under
  ``method="auto"``): local theta rows times replicated phi, tiled
  kernels per shard, the (B, K) weight product never materializes, and
  the uniforms come from the counter RNG (:mod:`repro.kernels.rng`)
  seeded by the replicated sweep key with *global* row counters — no
  per-shard key splits, no (B,) uniform transfers, and bit-identical
  draws whatever the device count.  The draw path contains **zero**
  cross-device collectives.
* **counts** — doc-topic counts are shard-local; the word-topic count
  matrix is the one quantity AD-LDA must synchronize, combined with a
  single explicit ``lax.psum`` (the only collective in the whole sweep —
  ``tests/test_sharded_sampler.py`` gates the jaxpr on exactly that).
* **theta/phi resample** — theta rows are updated locally (per-shard
  folded key: different shards must not reuse one gamma stream); phi is
  resampled identically on every shard from the replicated key and the
  all-reduced counts, so it stays replicated without a broadcast.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sampling
from repro.kernels import rng as _rng
from repro.lda.gibbs import LDAState, _counts, _update_phi, _update_theta
from repro.sampling.sharded import (
    _linear_index,
    data_axes,
    data_size,
    row_spec,
)


def _doc_sharded(mesh):
    return NamedSharding(mesh, row_spec(mesh))


def make_sharded_gibbs(mesh, K: int, V: int, alpha: float = 0.1,
                       beta: float = 0.05, method: str = "auto",
                       W: Optional[int] = None, sparse: bool = False,
                       cap: int = 32, mh_steps: int = 1):
    """Returns (place, step): ``place`` shards an LDAState + docs onto the
    mesh; ``step`` is the jitted shard_map'd sweep described above.

    ``sparse=True`` replaces the dense z-draw with the sparsity-aware MH
    sweep (:mod:`repro.lda.sparse`): each shard builds its fixed-width
    sparse doc-topic counts (static ``cap``, no retraces) from its own
    incoming z, proposes through the in-graph cdf word tables (the host
    alias builder cannot run inside ``shard_map``; the cdf build is one
    replicated O(VK) cumsum), and walks ``mh_steps`` MH cycles with
    *global* doc offsets — the counter RNG stays device-count invariant,
    and the sweep's only collective is still the single word-topic psum."""
    row = _doc_sharded(mesh)
    rep = NamedSharding(mesh, P())
    rs = row_spec(mesh)
    axes = data_axes(mesh)
    nd = data_size(mesh)

    def place(state: LDAState, docs, mask):
        return (
            LDAState(
                theta=jax.device_put(state.theta, row),
                phi=jax.device_put(state.phi, rep),
                z=jax.device_put(state.z, row),
                key=jax.device_put(state.key, rep),
                step=jax.device_put(state.step, rep),
            ),
            jax.device_put(jnp.asarray(docs), row),
            jax.device_put(jnp.asarray(mask), row),
        )

    def shard_step(theta, phi, z_old, key, step, docs, mask):
        C, N = docs.shape              # per-shard documents
        B = C * N
        kz, k_theta, k_phi, k_next = jax.random.split(key, 4)

        if sparse:
            # -- sparse MH z-draw: fixed-width sparse counts from the
            # incoming z, cdf word tables built in-graph, global doc
            # offsets keep the counter RNG topology-invariant.  Still
            # zero collectives in the draw.
            from repro.lda import sparse as _sparse

            cap_eff = min(cap, K)
            doc_topic0, _ = _sparse._counts_scatter(z_old, docs, mask, K, V)
            counts = _sparse.sparse_counts(doc_topic0, cap_eff)
            tbl_a = _sparse._phi_cdf(phi)
            tbl_b = jnp.zeros((1, 1), jnp.int32)
            seed = _rng.fold(_rng.seed_from_key(kz), _rng.TAG_SPARSE_MH)
            d0 = _linear_index(mesh) * C        # first global document
            z, _, _, _ = _sparse._mh_sweep(
                z_old, docs, mask, theta, phi, counts.ids, counts.cnt,
                tbl_a, tbl_b, seed, jnp.uint32(d0), jnp.float32(alpha),
                steps=mh_steps, cap=cap_eff, mode="cdf", chunk=min(256, C),
            )
        else:
            del z_old                  # replaced wholesale by this sweep
            # -- z-draw: factored plan per shard, counter RNG, no
            # collectives
            p = sampling.plan(
                (B, K), method=method, W=W, dtype=str(theta.dtype),
                has_key=False, factored=True, devices=nd,
            )
            words = docs.reshape(-1)
            doc_ids = jnp.arange(B, dtype=jnp.int32) // N
            row0 = _linear_index(mesh) * B      # first global word position
            seed = _rng.seed_from_key(kz)
            if p.method in sampling.FACTORED_VARIANTS:
                from repro.kernels.lda_draw import lda_draw_factored_rng

                idx = lda_draw_factored_rng(
                    theta, phi, doc_ids, words, seed, row_offset=row0,
                    W=p.W, tb=p.tb or 8,
                )
            else:
                dist = p.build_from_factors(theta, phi, words, doc_ids)
                u = _rng.row_uniforms(_rng.fold(seed, _rng.TAG_U, 0), row0, B)
                idx = p.draw(dist, u=u)
            z = idx.reshape(C, N)

        # -- counts: doc-topic local, word-topic all-reduced (AD-LDA's
        # one required synchronization)
        if sparse:
            from repro.lda import sparse as _sparse

            doc_topic, word_topic = _sparse._counts_scatter(
                z, docs, mask, K, V
            )
        else:
            doc_topic, word_topic = _counts(z, docs, mask, K, V)
        word_topic = jax.lax.psum(word_topic, axes)

        # -- resample: theta per shard (folded key — shards must not share
        # a gamma stream), phi identically on every shard (replicated)
        theta = _update_theta(
            jax.random.fold_in(k_theta, _linear_index(mesh)), doc_topic, alpha
        )
        phi = _update_phi(k_phi, word_topic, beta)
        return LDAState(theta=theta, phi=phi, z=z, key=k_next, step=step + 1)

    step_sm = jax.shard_map(
        shard_step,
        mesh=mesh,
        in_specs=(rs, P(), rs, P(), P(), rs, rs),
        out_specs=LDAState(theta=rs, phi=P(), z=rs, key=P(), step=P()),
        check_vma=False,  # pallas_call has no replication rule
    )

    @jax.jit
    def step(state: LDAState, docs, mask):
        return step_sm(
            state.theta, state.phi, state.z, state.key, state.step, docs, mask
        )

    return place, step
