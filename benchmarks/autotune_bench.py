"""Autotune benchmark: warm the tuning cache, then report auto-vs-fixed.

Two jobs:

  * ``python -m benchmarks.autotune_bench`` — measured-tune every (B, K)
    cell in the grid (persisting winners to the autotune cache), then time
    ``method="auto"`` against every fixed strategy and print the speedup
    of auto over each (>= 1.0 means auto matched or beat it; auto can
    trail the per-cell best by at most its own dispatch overhead).
  * ``python -m benchmarks.autotune_bench --import BENCH_sampler.json`` —
    pre-warm the cache from a ``sampler_bench --json`` run instead of
    re-timing anything here.

Prints the repo-standard ``name,us_per_call,derived`` CSV.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.sampler_bench import _bench
except ImportError:  # invoked as a script: benchmarks/ itself is on sys.path
    from sampler_bench import _bench
from repro import autotune
from repro.core import sample_categorical

FIXED = ("prefix", "fenwick", "two_level", "butterfly", "gumbel")


def warm(tuner: autotune.Tuner, Bs, Ks) -> int:
    """Measured-tune every grid cell into the tuning cache."""
    n = 0
    for B in Bs:
        for K in Ks:
            tuner.resolve(B, K, has_key=True)
            n += 1
    tuner.cache.save()
    return n


def report(tuner: autotune.Tuner, Bs, Ks):
    rows = []
    rng = np.random.default_rng(0)
    for B in Bs:
        for K in Ks:
            w = jnp.asarray(rng.uniform(0.1, 1.0, (B, K)), jnp.float32)
            key = jax.random.PRNGKey(0)
            method, W = tuner.resolve(B, K, has_key=True)
            fns = {
                "auto": jax.jit(
                    lambda w, k, m=method, W=W: sample_categorical(
                        w, key=k, method=m, W=W
                    )
                )
            }
            for m in FIXED:
                # fixed baselines run at their own default W (= the same
                # sqrt(K) heuristic), so vs_* isolates method choice
                fns[m] = jax.jit(
                    lambda w, k, m=m: sample_categorical(w, key=k, method=m)
                )
            times = {name: _bench(fn, w, key) * 1e6 for name, fn in fns.items()}
            rows.append(dict(B=B, K=K, winner=method, W=W, times=times))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--Bs", type=int, nargs="+", default=[1024, 4096])
    ap.add_argument("--Ks", type=int, nargs="+", default=[32, 256, 1024, 4096])
    ap.add_argument(
        "--import", dest="import_json", default=None, metavar="BENCH_JSON",
        help="pre-warm the cache from a sampler_bench --json file "
             "instead of measured tuning",
    )
    args = ap.parse_args(argv)

    tuner = autotune.get_tuner()
    if args.import_json:
        with open(args.import_json) as f:
            n = tuner.cache.ingest_records(json.load(f))
        tuner.cache.save()
        print(f"# imported {n} bucket winners from {args.import_json}")
    else:
        tuner = autotune.Tuner(cache=tuner.cache, mode="measure")
        n = warm(tuner, args.Bs, args.Ks)
        print(f"# measured-tuned {n} cells -> {tuner.cache.path}")

    print("name,us_per_call,derived")
    for r in report(tuner, args.Bs, args.Ks):
        t = r["times"]
        auto = t["auto"]
        speedups = ";".join(
            f"vs_{m}={t[m] / auto:.2f}x" for m in FIXED
        )
        print(
            f"autotune_B{r['B']}_K{r['K']},{auto:.0f},"
            f"winner={r['winner']}(W={r['W']});{speedups}"
        )


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
