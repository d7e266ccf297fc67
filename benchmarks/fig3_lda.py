"""Paper Figure 3 reproduction + corpus-scale sparse-vs-dense LDA bench.

Legacy mode (no args): the paper's K-sweep.  The paper measures a full
LDA Gibbs application on a Titan Black GPU and shows the butterfly
variant >2x faster than the prefix-sum variant for K >= 200.  On this
CPU container we measure the same *algorithmic* variants (vectorized
JAX) on a scaled-down corpus and report wall time per Gibbs sweep + the
butterfly/prefix ratio; the hardware-grounded statement of the paper's
claim on TPU (HBM-byte model) is derived alongside:

    bytes_prefix    ~ B*K reads + B*K prefix writes + search re-reads
    bytes_butterfly ~ B*K reads + B*(K/W) block sums + B*W block re-read

so predicted traffic ratio ~= 3K / (K + K/W + W) -> ~3x for K >> W, which
is the paper's >2x end-to-end once non-sampling phases dilute it.

Scale mode (``--docs/--vocab/--topics``): times the dense factored path
against the sparse MH-alias sweep (ISSUE 8) on a Zipf corpus and emits
``BENCH_lda.json`` rows in the ``repro-autotune-bench-v1`` schema that
``check_regression.py`` matches on (``method``/``B``/``K``/``W``/
``devices``/``us``), decorated with tokens/sec, per-token ns, and the
K_d/K_w live-topic occupancy that explains the win.  ``--stream`` runs
the host-streamed sweep over a generated shard source instead (the
million-doc path; the weekly CI job runs it at 10^6 docs).

    python benchmarks/fig3_lda.py --docs 256 --vocab 1024 --topics 512 \\
        --sparse --sweeps 3 --json BENCH_lda.json
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.lda import gibbs_step, init_state, synthesize_corpus
from repro.lda.corpus import zipf_shard_source
from repro.lda.gibbs import draw_z
from repro.lda import sparse as lda_sparse

BENCH_SCHEMA = "repro-autotune-bench-v1"


def _time_sweep(state, corpus, method, W, iters=3):
    # warmup (compile)
    s = gibbs_step(state, corpus, method=method, W=W)
    jax.block_until_ready(s.theta)
    t0 = time.perf_counter()
    for _ in range(iters):
        s = gibbs_step(s, corpus, method=method, W=W)
        jax.block_until_ready(s.theta)
    return (time.perf_counter() - t0) / iters, s


def run(scale=0.004, ks=(16, 48, 80, 112, 144, 176, 208, 240), iters=3):
    rows = []
    corpus = synthesize_corpus(
        seed=0,
        M=max(64, int(43556 * scale)),
        V=max(128, int(37286 * scale)),
        K=16,
        avg_len=70.5,
        max_len=307,
    )
    for K in ks:
        state = init_state(jax.random.PRNGKey(K), corpus, K)
        t_prefix, _ = _time_sweep(state, corpus, "prefix", 32, iters)
        t_bfly, _ = _time_sweep(state, corpus, "butterfly", 32, iters)
        t_fenwick, _ = _time_sweep(state, corpus, "fenwick", 32, iters)
        W2 = 16 if K <= 300 else 32
        t_two, _ = _time_sweep(state, corpus, "two_level", W2, iters)
        W = 32
        model_ratio = 3 * K / (K + K / W + W)
        rows.append(
            dict(
                K=K,
                prefix_ms=t_prefix * 1e3,
                butterfly_ms=t_bfly * 1e3,
                fenwick_ms=t_fenwick * 1e3,
                two_level_ms=t_two * 1e3,
                cpu_ratio=t_prefix / t_bfly,
                cpu_ratio_two_level=t_prefix / t_two,
                tpu_traffic_model_ratio=model_ratio,
            )
        )
    return rows


def legacy_main():
    print("name,us_per_call,derived")
    for r in run():
        print(
            f"fig3_lda_K{r['K']},{r['butterfly_ms']*1e3:.0f},"
            f"prefix_ms={r['prefix_ms']:.1f};butterfly_ms={r['butterfly_ms']:.1f};"
            f"fenwick_ms={r['fenwick_ms']:.1f};two_level_ms={r['two_level_ms']:.1f};"
            f"cpu_ratio={r['cpu_ratio']:.2f};"
            f"cpu_ratio_two_level={r['cpu_ratio_two_level']:.2f};"
            f"traffic_model_ratio={r['tpu_traffic_model_ratio']:.2f}"
        )


# ---------------------------------------------------------------------------
# Scale mode: sparse-vs-dense rows for BENCH_lda.json
# ---------------------------------------------------------------------------


def _occupancy(state, corpus):
    """K_d / K_w live-topic stats from the current z assignments."""
    K = state.theta.shape[-1]
    V = state.phi.shape[0]
    doc_topic, word_topic = lda_sparse._counts_scatter(
        jnp.asarray(state.z), jnp.asarray(corpus.docs),
        jnp.asarray(corpus.mask), K, V,
    )
    kd = np.asarray((np.asarray(doc_topic) > 0).sum(axis=1))
    wt = np.asarray(word_topic)
    occurs = wt.sum(axis=1) > 0
    kw = (wt[occurs] > 0).sum(axis=1) if occurs.any() else np.zeros(1)
    return {
        "kd_mean": float(kd.mean()),
        "kd_max": int(kd.max()),
        "kw_mean": float(kw.mean()),
    }


def _timeit(fn, iters=3, warmup=1):
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn())
    times = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _row(method, tokens, K, seconds, extra=None):
    rec = {
        "method": method,
        "B": int(tokens),
        "K": int(K),
        "W": 0,
        "devices": 1,
        "us": seconds * 1e6,
        "tokens_per_sec": tokens / seconds if seconds > 0 else 0.0,
        "ns_per_token": seconds * 1e9 / max(tokens, 1),
    }
    if extra:
        rec.update(extra)
    return rec


def bench_scale(docs, vocab, topics, sweeps, sparse, iters=3, seed=0):
    """Dense-vs-sparse rows at one (docs, vocab, topics) shape."""
    corpus = synthesize_corpus(
        seed, M=docs, V=vocab, K=min(topics, 64), avg_len=64, max_len=256,
        zipf_exponent=1.05, doc_concentration=0.1,
    )
    tokens = corpus.total_words
    K = topics
    print(
        f"# corpus: {docs} docs, V={vocab}, K={K}, {tokens} tokens (Zipf)",
        file=sys.stderr,
    )
    state = init_state(jax.random.PRNGKey(seed), corpus, K)
    records = []

    # burn in so occupancy reflects a mixing chain, then record sweeps.
    # dense sweep (the factored lda_kernel path under auto).
    t_dense_sweep, state_d = _time_sweep(state, corpus, "auto", None, iters)
    records.append(_row("lda_dense_sweep", tokens, K, t_dense_sweep))

    extra = _occupancy(state_d, corpus)
    if sparse:
        cache = lda_sparse.SparseSweepCache()
        s = gibbs_step(state, corpus, sparse=True, sparse_cache=cache,
                       mh_steps=1, word_proposal="cdf")
        jax.block_until_ready(s.theta)
        for _ in range(max(sweeps - 1, 0)):
            s = gibbs_step(s, corpus, sparse=True, sparse_cache=cache,
                           mh_steps=1, word_proposal="cdf")
        jax.block_until_ready(s.theta)
        t0 = time.perf_counter()
        for _ in range(iters):
            s = gibbs_step(s, corpus, sparse=True, sparse_cache=cache,
                           mh_steps=1, word_proposal="cdf")
            jax.block_until_ready(s.theta)
        t_sparse_sweep = (time.perf_counter() - t0) / iters
        occ = _occupancy(s, corpus)
        occ["cap"] = cache.cap
        occ.update({f"accept_{k}": v for k, v in (cache.last_stats or {}).items()})
        records.append(_row("lda_sparse_sweep", tokens, K, t_sparse_sweep, occ))

        # draw-phase rows: the apples-to-apples z-draw comparison the
        # >=3x acceptance criterion gates.  Tables and sparse counts are
        # prebuilt and the sweep kernel timed directly — that is the
        # amortized training regime (one O(VK) table build per sweep
        # spread over the whole corpus; at paper scale ~3M tokens the
        # build is noise, and on this deliberately tiny bench corpus
        # timing it per-draw would swamp the per-token cost).  The build
        # is reported separately as table_build_ms.
        docs_j = jnp.asarray(corpus.docs)
        mask_j = jnp.asarray(corpus.mask)
        t_dense_draw = _timeit(
            lambda: draw_z(state_d, docs_j, method="lda_kernel"), iters
        )
        records.append(
            _row("lda_dense", tokens, K, t_dense_draw, extra)
        )
        from repro.kernels import rng as _rng

        V = corpus.vocab_size
        cap = min(cache.cap or 32, K)
        doc_topic, _ = lda_sparse._counts_scatter(
            s.z, docs_j, mask_j, K, V
        )
        counts = lda_sparse.sparse_counts(doc_topic, cap)
        seed = _rng.fold(_rng.seed_from_key(s.key), _rng.TAG_SPARSE_MH)
        # one MH cycle per row: the unit the dense draw is compared
        # against (mh_steps multiplies cost linearly; the sweep rows
        # above carry the training default end to end)
        for mode in ("alias", "alias_device", "cdf"):
            t0 = time.perf_counter()
            tbl_a, tbl_b = lda_sparse.word_proposal_tables(s.phi, mode)
            jax.block_until_ready(tbl_a)
            t_build = time.perf_counter() - t0
            for steps in (1,):
                fn = lda_sparse._mh_sweep_jit(steps, cap, mode, 256)
                args = (
                    s.z, docs_j, mask_j, s.theta, s.phi,
                    counts.ids, counts.cnt, tbl_a, tbl_b, seed,
                    jnp.uint32(0), jnp.float32(0.1),
                )
                t_sp = _timeit(lambda: fn(*args), iters)
                ratio = t_dense_draw / t_sp if t_sp > 0 else 0.0
                records.append(
                    _row(f"lda_sparse_{mode}_mh{steps}", tokens, K, t_sp,
                         dict(occ, speedup_vs_dense=round(ratio, 2),
                              table_build_ms=round(t_build * 1e3, 2),
                              cap=cap))
                )
                print(
                    f"# K={K} draw: dense {t_dense_draw*1e3:.1f} ms, "
                    f"sparse {mode} mh{steps} {t_sp*1e3:.1f} ms "
                    f"({ratio:.2f}x)",
                    file=sys.stderr,
                )

        # training-regime rows: phi is resampled EVERY sweep, so the
        # word-proposal table is rebuilt every sweep and per-token time
        # includes the build — the cdf descent against the device alias
        # build (DESIGN.md §11)
        train_us = {}
        for mode in ("cdf", "alias_device"):
            fn = lda_sparse._mh_sweep_jit(1, cap, mode, 256)
            # distinct phi per iteration defeats the digest-keyed table
            # LRU — each build is a real rebuild, as in training
            phis = [s.phi * (1.0 + 1e-6 * i) for i in range(iters + 1)]
            for ph in phis:
                jax.block_until_ready(ph)

            def one_sweep(ph):
                ta, tb = lda_sparse.word_proposal_tables(ph, mode)
                return fn(s.z, docs_j, mask_j, s.theta, ph,
                          counts.ids, counts.cnt, ta, tb, seed,
                          jnp.uint32(0), jnp.float32(0.1))

            jax.block_until_ready(one_sweep(phis[0]))  # compile
            times = []
            for ph in phis[1:]:
                t0 = time.perf_counter()
                jax.block_until_ready(one_sweep(ph))
                times.append(time.perf_counter() - t0)
            t_train = float(np.median(times))
            train_us[mode] = t_train
            records.append(
                _row(f"lda_sparse_train_{mode}", tokens, K, t_train,
                     dict(cap=cap, build_included=True))
            )
        print(
            f"# K={K} train (build+sweep): cdf "
            f"{train_us['cdf']*1e3:.1f} ms, alias_device "
            f"{train_us['alias_device']*1e3:.1f} ms "
            f"({train_us['cdf']/train_us['alias_device']:.2f}x)",
            file=sys.stderr,
        )
    return records


def bench_train(docs, vocab, topics, iters=3, mh_steps=4, seed=0):
    """Training-regime rows at a scale where the device build amortizes.

    Unlike :func:`bench_scale` this skips the dense sweep entirely: at
    the token counts where alias_device pays for its per-sweep table
    rebuild (draws-per-refresh d = tokens*mh/V above the ~2K CPU
    crossover, DESIGN.md §11) a dense K-wide sweep would take minutes
    and gates nothing.  Each timed sweep rebuilds the word-proposal
    table from a fresh phi — the honest training cost.
    """
    corpus = synthesize_corpus(
        seed, M=docs, V=vocab, K=min(topics, 64), avg_len=96, max_len=384,
        zipf_exponent=1.05, doc_concentration=0.1,
    )
    tokens = corpus.total_words
    K = topics
    V = corpus.vocab_size
    print(
        f"# train corpus: {docs} docs, V={V}, K={K}, {tokens} tokens, "
        f"mh_steps={mh_steps}",
        file=sys.stderr,
    )
    state = init_state(jax.random.PRNGKey(seed), corpus, K)
    cache = lda_sparse.SparseSweepCache()
    s = gibbs_step(state, corpus, sparse=True, sparse_cache=cache,
                   mh_steps=1, word_proposal="cdf")
    jax.block_until_ready(s.theta)

    from repro.kernels import rng as _rng

    docs_j = jnp.asarray(corpus.docs)
    mask_j = jnp.asarray(corpus.mask)
    cap = min(cache.cap or 32, K)
    doc_topic, _ = lda_sparse._counts_scatter(s.z, docs_j, mask_j, K, V)
    counts = lda_sparse.sparse_counts(doc_topic, cap)
    seed_u = _rng.fold(_rng.seed_from_key(s.key), _rng.TAG_SPARSE_MH)

    records = []
    train_t = {}
    for mode in ("cdf", "alias_device"):
        fn = lda_sparse._mh_sweep_jit(mh_steps, cap, mode, 256)
        # distinct phi per iteration defeats the digest-keyed table LRU
        phis = [s.phi * (1.0 + 1e-6 * i) for i in range(iters + 1)]
        for ph in phis:
            jax.block_until_ready(ph)

        def one_sweep(ph):
            ta, tb = lda_sparse.word_proposal_tables(ph, mode)
            return fn(s.z, docs_j, mask_j, s.theta, ph,
                      counts.ids, counts.cnt, ta, tb, seed_u,
                      jnp.uint32(0), jnp.float32(0.1))

        jax.block_until_ready(one_sweep(phis[0]))  # compile
        times = []
        for ph in phis[1:]:
            t0 = time.perf_counter()
            jax.block_until_ready(one_sweep(ph))
            times.append(time.perf_counter() - t0)
        t_train = float(np.median(times))
        train_t[mode] = t_train
        records.append(
            _row(f"lda_train_{mode}_mh{mh_steps}", tokens, K, t_train,
                 dict(cap=cap, mh_steps=mh_steps, vocab=V,
                      build_included=True))
        )
        print(
            f"# train {mode}: {t_train*1e3:.1f} ms/sweep "
            f"({t_train*1e9/max(tokens, 1):.0f} ns/token, build included)",
            file=sys.stderr,
        )
    ratio = train_t["cdf"] / train_t["alias_device"]
    print(
        f"# K={K} training sweep: alias_device {ratio:.2f}x vs cdf",
        file=sys.stderr,
    )
    return records


def bench_stream(num_docs, vocab, topics, sweeps, seed=0):
    """Host-streamed sweep rows (the million-doc path)."""
    src = zipf_shard_source(
        seed, num_docs=num_docs, V=vocab, K=topics,
        shard_docs=min(8192, num_docs), avg_len=64, max_len=256,
    )
    eng = lda_sparse.StreamingSparseLDA(
        jax.random.PRNGKey(seed), src, K=topics, mh_steps=1,
        word_proposal="cdf",
    )
    records = []
    for i in range(max(sweeps, 1)):
        stats = eng.sweep()
        print(
            f"# stream sweep {i}: {stats['tokens']} tokens, "
            f"{stats['tokens_per_sec']:.0f} tok/s, "
            f"perplexity {stats['perplexity']:.1f}",
            file=sys.stderr,
        )
        if i > 0:  # sweep 0 pays compilation
            records.append(
                _row("lda_sparse_stream", stats["tokens"], topics,
                     stats["seconds"],
                     {"num_docs": num_docs, "perplexity": stats["perplexity"]})
            )
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--docs", type=int, default=None,
                    help="corpus documents (enables scale mode)")
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--topics", type=int, default=512,
                    help="model K (comma-separate for a sweep, e.g. 512,1024)")
    ap.add_argument("--sparse", action="store_true",
                    help="include the sparse MH rows (scale mode)")
    ap.add_argument("--sweeps", type=int, default=3)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--stream", action="store_true",
                    help="run the host-streamed sweep instead (million-doc)")
    ap.add_argument("--train", action="store_true",
                    help="training-regime rows only (phi rebuilt per sweep, "
                         "no dense baseline)")
    ap.add_argument("--mh-steps", type=int, default=4,
                    help="MH proposals per token in --train mode")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write BENCH_lda.json-style records here")
    args = ap.parse_args(argv)

    if args.docs is None and not (args.stream or args.train):
        legacy_main()
        return 0

    records = []
    for K in (int(k) for k in str(args.topics).split(",")):
        if args.train:
            records.extend(
                bench_train(args.docs or 16384, args.vocab, K,
                            args.iters, args.mh_steps)
            )
        elif args.stream:
            records.extend(
                bench_stream(args.docs or 100_000, args.vocab, K, args.sweeps)
            )
        else:
            records.extend(
                bench_scale(args.docs, args.vocab, K, args.sweeps,
                            args.sparse, args.iters)
            )
    blob = {
        "schema": BENCH_SCHEMA,
        "backend": jax.default_backend(),
        "records": records,
    }
    out = json.dumps(blob, indent=1)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
        print(f"# wrote {len(records)} records to {args.json}", file=sys.stderr)
    else:
        print(out)
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    sys.exit(main())
