"""Sampler micro-benchmark: throughput of each drawing strategy over a
(B, K) grid — the paper's core operation isolated from LDA.

Reports us per draw-batch and draws/s; plus the derived HBM-traffic model
(bytes per sample) that grounds the TPU prediction for each method.

``run_fused`` additionally benches the tiled fused factored z-draw (the
``lda_kernel`` path: theta-phi weights never materialize) against the
materializing gather-multiply-then-sample pipeline — the Gibbs-sweep
restatement of the paper's headline comparison; rows land under
``fused_factored`` in the JSON.

Also writes ``BENCH_sampler.json`` (path via ``--json PATH``, suppress
with ``--no-json``) — per-method timing records in the
``repro-autotune-bench-v1`` schema that ``check_regression.py`` reads.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sample_categorical
from repro.kernels import runtime

BENCH_SCHEMA = "repro-autotune-bench-v1"

METHODS = ("prefix", "butterfly", "fenwick", "two_level", "gumbel")


def _bench(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def traffic_model_bytes(K: int, W: int, method: str) -> float:
    """Predicted HBM bytes per sample on TPU (fp32)."""
    if method == "prefix":
        return 4 * (K + K + np.log2(max(K, 2)) * 128)  # read + write prefix + search lines
    if method in ("butterfly", "fenwick", "two_level"):
        return 4 * (K + K / W + W)                      # read + block sums + one block
    if method == "gumbel":
        return 4 * K                                    # one pass (but K RNG + log on VPU)
    return 4 * K


def run(Bs=(4096,), Ks=(64, 256, 1024, 4096), W=32, iters=5):
    rows = []
    rng = np.random.default_rng(0)
    for B in Bs:
        for K in Ks:
            w = jnp.array(rng.uniform(0.1, 1.0, size=(B, K)).astype(np.float32))
            u = jnp.array(rng.uniform(0, 1, size=(B,)).astype(np.float32))
            key = jax.random.PRNGKey(0)
            for method in METHODS:
                if method == "gumbel":
                    fn = jax.jit(lambda w, k: sample_categorical(w, key=k, method="gumbel"))
                    t = _bench(fn, w, key, iters=iters)
                else:
                    fn = jax.jit(
                        lambda w, u, m=method: sample_categorical(w, u=u, method=m, W=W)
                    )
                    t = _bench(fn, w, u, iters=iters)
                rows.append(
                    dict(
                        B=B, K=K, method=method, us=t * 1e6,
                        draws_per_s=B / t,
                        model_bytes_per_sample=traffic_model_bytes(K, W, method),
                    )
                )
    return rows


def run_fused(Bs=(4096,), Ks=(256, 1024, 4096), W=32, iters=5):
    """The tiled fused factored z-draw (the LDA hot loop: weights never
    materialize) vs. the materializing pipeline (gather factor rows, form
    the (B, K) product, then the two-level draw) at the same workload.

    This is the paper's headline comparison restated for the Gibbs sweep:
    the fused path should be no slower anywhere and win once K is large
    enough that the (B, K) round-trip dominates (K >= ~256)."""
    from repro.core.butterfly import draw_two_level
    from repro.kernels.lda_draw import lda_draw_factored

    rows = []
    rng = np.random.default_rng(1)
    for B in Bs:
        for K in Ks:
            C, V = max(1, B // 16), 512
            theta = jnp.array(rng.uniform(0.1, 1.0, (C, K)).astype(np.float32))
            phi = jnp.array(rng.uniform(0.1, 1.0, (V, K)).astype(np.float32))
            doc_ids = jnp.array(rng.integers(0, C, B), jnp.int32)
            words = jnp.array(rng.integers(0, V, B), jnp.int32)
            u = jnp.array(rng.uniform(0, 1, B).astype(np.float32))
            tb = runtime.default_tb(B)

            fused = jax.jit(
                lambda th, ph, uu: lda_draw_factored(
                    th, ph, doc_ids, words, uu, W=W, tb=tb
                )
            )

            def mat_fn(th, ph, uu):
                flat = th[doc_ids] * ph[words]          # the (B, K) round-trip
                return draw_two_level(flat, uu, W=W)

            mat = jax.jit(mat_fn)
            t_f = _bench(fused, theta, phi, u, iters=iters)
            t_m = _bench(mat, theta, phi, u, iters=iters)
            rows.append(
                dict(
                    B=B, K=K, W=W, tb=tb, method="lda_kernel",
                    us=t_f * 1e6, materializing_us=t_m * 1e6,
                    speedup=t_m / t_f,
                )
            )
    return rows


def run_decode(Bs=(256,), Ks=(4096, 16384), W=32, iters=5):
    """Truncated decode (top-k 64 + top-p 0.9, the llama/gemma-style
    serving default) at vocab-scale K: the butterfly-native threshold
    path (value-axis bisection + masked block sums — no sort, no (B, K)
    sorted copy; the fused kernel on TPU, the XLA twin elsewhere) vs the
    classic sort-then-sample pipeline (descending sort, cumsum scan,
    mask, prefix draw).  Rows land under ``decode`` in the JSON and as
    ``trunc_fused`` / ``trunc_sorted`` records the CI perf gate tracks."""
    from repro import sampling
    from repro.sampling import reference as sref
    from repro.sampling import transforms as str_

    rows = []
    rng = np.random.default_rng(3)
    for B in Bs:
        for K in Ks:
            logits = jnp.array(rng.normal(0, 2.0, (B, K)).astype(np.float32))
            u = jnp.array(rng.uniform(0, 1, B).astype(np.float32))
            key = jax.random.PRNGKey(0)
            ch = str_.chain(top_k=64, top_p=0.9)
            sig = str_.signature(ch)          # "kp": what actually runs
            p = sampling.plan((B, K), method="auto", transforms=sig)

            fused = jax.jit(
                lambda z, k: p.sample_logits(z, k, temperature=0.8,
                                             transforms=ch)
            )

            def sorted_fn(z, uu):
                w = sampling.logits_to_weights(z, 0.8)
                return sref.draw_truncated_sorted(w, uu, ch)

            srt = jax.jit(sorted_fn)
            t_f = _bench(fused, logits, key, iters=iters)
            t_s = _bench(srt, logits, u, iters=iters)
            rows.append(
                dict(
                    B=B, K=K, W=p.W, tb=p.tb, tk=p.tk, method="trunc_fused",
                    us=t_f * 1e6, sorted_us=t_s * 1e6, speedup=t_s / t_f,
                    transforms=sig, resolved=p.method,
                )
            )
            rows.append(
                dict(
                    B=B, K=K, W=W, method="trunc_sorted", us=t_s * 1e6,
                    transforms=sig,
                )
            )
    return rows


def run_shard(B_per=1024, Ks=(256, 1024), W=32, iters=5, method="two_level"):
    """Mesh-sharded draw scaling: the same per-shard (B_per, K) workload
    on a 1-device mesh vs. every available device (virtual CPU devices
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    The sharded path runs one shard_map of the tiled kernels with counter
    RNG — zero collectives — so per-device draw time stays within ~1.3x
    of the single-device figure as long as every shard has a core to run
    on (per-shard work is identical; the residual is dispatch fan-out).
    Virtual CPU devices beyond the physical core count time-share cores,
    so the full-device row additionally reports ``oversubscription`` =
    devices / cores — judge the 1.3x bound on rows where it is <= 1.
    Rows carry a ``devices`` field.
    """
    import os

    from jax.sharding import Mesh

    from repro import sampling

    devs = jax.devices()
    cores = os.cpu_count() or 1
    rng = np.random.default_rng(2)
    rows = []
    for n in sorted({1, min(len(devs), cores), len(devs)}):
        mesh = Mesh(np.array(devs[:n]), ("data",))
        for K in Ks:
            B = B_per * n
            w = jnp.array(rng.uniform(0.1, 1.0, (B, K)).astype(np.float32))
            p = sampling.plan((B, K), method=method, W=W, mesh=mesh)
            ws = sampling.sharded.place_rows(mesh, w)
            key = jax.random.PRNGKey(0)
            t = _bench(lambda: p.sample(ws, key=key), iters=iters)
            rows.append(
                dict(
                    B=B_per, K=K, W=p.W, tb=p.tb, tk=p.tk, devices=n,
                    method=method, us=t * 1e6, draws_per_s=B / t,
                    global_B=B, oversubscription=n / cores,
                )
            )
    base = {
        (r["B"], r["K"]): r["us"] for r in rows if r["devices"] == 1
    }
    for r in rows:
        r["vs_single_device"] = r["us"] / base[(r["B"], r["K"])]
    return rows


def run_reuse(B=4096, K=4096, W=32, draws=16):
    """Build-once/draw-many through the distribution-object API vs. the
    one-shot shim: the amortization the ``Categorical`` pytree exists for.

    Returns rows comparing ``draws`` one-shot calls (table rebuilt every
    time) against one ``plan().build()`` plus ``draws`` ``draw()`` calls
    from the held distribution."""
    from repro import sampling

    rng = np.random.default_rng(0)
    w = jnp.array(rng.uniform(0.1, 1.0, size=(B, K)).astype(np.float32))
    keys = jax.random.split(jax.random.PRNGKey(0), draws)
    rows = []
    for method in ("fenwick", "two_level", "alias"):
        p = sampling.plan((B, K), method=method, W=W, draws=draws)

        def oneshot():
            outs = [
                sample_categorical(w, key=k, method=method, W=W) for k in keys
            ]
            return outs[-1]

        dist = p.build(w)

        def reused():
            outs = [p.draw(dist, key=k) for k in keys]
            return outs[-1]

        t_one = _bench(oneshot, iters=3)
        t_reuse = _bench(reused, iters=3)
        rows.append(
            dict(
                B=B, K=K, method=method, draws=draws,
                oneshot_us=t_one * 1e6, reused_us=t_reuse * 1e6,
                speedup=t_one / t_reuse,
            )
        )
    return rows


def run_zoo(B=1024, Ks=(256, 1024, 4096), iters=5):
    """Frozen-distribution strategy-zoo rows (DESIGN.md §11): the
    merged-rank on-device alias build, its O(1) draw, the radix-forest
    draw, and the device-build vs host-build+ingest comparison the
    acceptance gate tracks — the host figure is what ``alias`` pays on
    every refresh (numpy Vose pack + table transfer + sync), the device
    figure is the closed-jaxpr rebuild ``alias_device`` runs in-graph."""
    from repro import sampling
    from repro.core import alias as _alias
    from repro.kernels.alias_build import build_alias_tables_device

    rows = []
    rng = np.random.default_rng(4)
    key = jax.random.PRNGKey(0)
    for K in Ks:
        w = jnp.array(rng.uniform(0.1, 1.0, (B, K)).astype(np.float32))
        build_dev = jax.jit(build_alias_tables_device)
        t_dev = _bench(build_dev, w, iters=iters)
        w_host = np.asarray(w)

        def host_build():
            t = _alias.build_alias_tables_host(w_host)
            return (t.prob, t.alias)

        t_host = _bench(host_build, iters=max(2, iters // 2))
        row = dict(
            B=B, K=K, method="alias_device_build", us=t_dev * 1e6,
            host_build_us=t_host * 1e6,
            build_speedup_vs_host=t_host / t_dev,
        )
        if t_host / t_dev < 2.0 and K >= 1024:
            row["note"] = (
                "device build under 2x vs host here: XLA CPU gather "
                "throughput bounds the bisection passes on this host; "
                "the device build remains the only in-graph option "
                "(refresh inside jit/shard_map)"
            )
        rows.append(row)
        for method in ("alias_device", "radix_forest"):
            p = sampling.plan((B, K), method=method, draws=16)
            dist = p.build(w)
            jax.block_until_ready(dist.state)
            t = _bench(lambda k: p.draw(dist, key=k), key, iters=iters)
            rows.append(
                dict(B=B, K=K, method=method, us=t * 1e6, draws_per_s=B / t)
            )
    return rows


def write_json(rows, fused_rows=None, path: str = "BENCH_sampler.json",
               W: int = 32, shard_rows=None, decode_rows=None,
               zoo_rows=None) -> str:
    """Emit the rows as bench records.  Fused-vs-materializing rows land
    both in ``records`` (the fused timing) and, with their materializing
    counterpart, under ``fused_factored``.  Every record carries a
    ``devices`` field (1 for the single-device grids; the ``--shard``
    rows record their mesh size and B is per-shard) — readers that
    predate the field ignore it."""
    backend = jax.default_backend()

    def _rec(r, W, method, us):
        tb, tk = runtime.default_tb(r["B"]), runtime.default_tk(r["K"], W)
        rec = {
            "backend": backend, "B": r["B"], "K": r["K"],
            "W": r.get("W", W), "tb": r.get("tb", tb), "tk": r.get("tk", tk),
            "draws": 1, "dtype": "float32", "method": method, "us": us,
            "devices": r.get("devices", 1),
        }
        if r.get("transforms"):
            rec["transforms"] = r["transforms"]
        return rec

    blob = {
        "schema": BENCH_SCHEMA,
        "backend": backend,
        "records": [_rec(r, W, r["method"], r["us"]) for r in rows]
        + [_rec(r, W, r["method"], r["us"]) for r in (fused_rows or [])]
        + [_rec(r, W, r["method"], r["us"]) for r in (shard_rows or [])]
        + [_rec(r, W, r["method"], r["us"]) for r in (decode_rows or [])]
        + [_rec(r, W, r["method"], r["us"]) for r in (zoo_rows or [])],
        "fused_factored": [
            {
                "B": r["B"], "K": r["K"], "W": r["W"], "tb": r["tb"],
                "fused_us": r["us"], "materializing_us": r["materializing_us"],
                "speedup": r["speedup"],
            }
            for r in (fused_rows or [])
        ],
        "sharded": [
            {
                "B": r["B"], "K": r["K"], "devices": r["devices"],
                "us": r["us"], "vs_single_device": r["vs_single_device"],
                "oversubscription": r["oversubscription"],
            }
            for r in (shard_rows or [])
        ],
        "decode": [
            {
                "B": r["B"], "K": r["K"], "W": r["W"],
                "resolved": r["resolved"], "fused_us": r["us"],
                "sorted_us": r["sorted_us"], "speedup": r["speedup"],
            }
            for r in (decode_rows or [])
            if r["method"] == "trunc_fused"
        ],
        "strategy_zoo": [
            {k: v for k, v in r.items()}
            for r in (zoo_rows or [])
        ],
    }
    with open(path, "w") as f:
        json.dump(blob, f, indent=1)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_sampler.json", metavar="PATH",
                    help="where to write the bench records")
    ap.add_argument("--no-json", action="store_true",
                    help="CSV to stdout only, write no file")
    ap.add_argument("--reuse", action="store_true",
                    help="also benchmark build-once/draw-many (Categorical "
                         "reuse) against the one-shot shim")
    ap.add_argument("--shard", action="store_true",
                    help="also benchmark the mesh-sharded draw path on all "
                         "available devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 for "
                         "virtual CPU devices)")
    ap.add_argument("--shard-only", action="store_true",
                    help="run ONLY the sharded scaling rows — use this in "
                         "a separate virtual-device process so the flag "
                         "never skews the single-device grids")
    ap.add_argument("--decode", action="store_true",
                    help="also benchmark truncated decode (top-k/top-p via "
                         "the butterfly threshold path) against the "
                         "sort-then-sample baseline at vocab-scale K")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run: fewer iterations and shapes")
    args = ap.parse_args(argv)
    if args.shard_only and args.json == "BENCH_sampler.json":
        # don't clobber the single-device grid file with a shard-only blob
        args.json = "BENCH_sampler_shard.json"
    iters = 2 if args.quick else 5
    Ks = (256, 1024) if args.quick else (64, 256, 1024, 4096)
    Bs = (1024,) if args.quick else (4096,)
    rows, fused_rows, decode_rows, zoo_rows = [], [], [], []
    if not args.shard_only:
        rows = run(Bs=Bs, Ks=Ks, iters=iters)
        fused_rows = run_fused(Bs=Bs, Ks=tuple(k for k in Ks if k >= 256),
                               iters=iters)
        # the strategy-zoo grid is fixed (the acceptance gate tracks
        # K in {256, 1024, 4096}); --quick only trims iterations
        zoo_rows = run_zoo(B=Bs[0], iters=iters)
    if args.decode and not args.shard_only:
        decode_rows = run_decode(
            Bs=(64,) if args.quick else (256,),
            Ks=(4096,) if args.quick else (4096, 16384),
            iters=iters,
        )
    shard_rows = None
    if args.shard or args.shard_only:
        shard_rows = run_shard(
            B_per=256 if args.quick else 1024,
            Ks=(256,) if args.quick else (256, 1024), iters=iters,
        )
    print("name,us_per_call,derived")
    for r in rows:
        print(
            f"sampler_{r['method']}_B{r['B']}_K{r['K']},{r['us']:.0f},"
            f"draws_per_s={r['draws_per_s']:.3g};"
            f"model_bytes_per_sample={r['model_bytes_per_sample']:.0f}"
        )
    for r in fused_rows:
        print(
            f"fused_factored_B{r['B']}_K{r['K']},{r['us']:.0f},"
            f"materializing_us={r['materializing_us']:.0f};"
            f"speedup={r['speedup']:.2f}x"
        )
    for r in decode_rows:
        if r["method"] != "trunc_fused":
            continue
        print(
            f"trunc_decode_B{r['B']}_K{r['K']},{r['us']:.0f},"
            f"sorted_us={r['sorted_us']:.0f};speedup={r['speedup']:.2f}x;"
            f"resolved={r['resolved']}"
        )
    for r in zoo_rows:
        if r["method"] == "alias_device_build":
            print(
                f"zoo_build_B{r['B']}_K{r['K']},{r['us']:.0f},"
                f"host_build_us={r['host_build_us']:.0f};"
                f"vs_host={r['build_speedup_vs_host']:.2f}x"
            )
        else:
            print(
                f"zoo_{r['method']}_B{r['B']}_K{r['K']},{r['us']:.0f},"
                f"draws_per_s={r['draws_per_s']:.3g}"
            )
    if shard_rows:
        for r in shard_rows:
            print(
                f"shard_{r['method']}_B{r['B']}_K{r['K']}_dev{r['devices']},"
                f"{r['us']:.0f},draws_per_s={r['draws_per_s']:.3g};"
                f"vs_single_device={r['vs_single_device']:.2f}x"
            )
    if args.reuse:
        for r in run_reuse():
            print(
                f"reuse_{r['method']}_B{r['B']}_K{r['K']}_d{r['draws']},"
                f"{r['reused_us']:.0f},oneshot_us={r['oneshot_us']:.0f};"
                f"speedup={r['speedup']:.2f}x"
            )
    if not args.no_json:
        path = write_json(rows, fused_rows, args.json, shard_rows=shard_rows,
                          decode_rows=decode_rows, zoo_rows=zoo_rows)
        print(f"# wrote {path} ({BENCH_SCHEMA})")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
