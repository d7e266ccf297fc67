#!/usr/bin/env python3
"""Find the highest rate an open-loop serving cell sustains.

    python3 bench/knee.py --workload qwen3-4b.chat --rates 1.0 1.2 1.4 \
        --seconds 51 --seeds 7 8 [--drain 15] [--out knee.jsonl]

Runs the cell's traffic once per offered rate and seed, in this one process,
and prints for each: requests sent and failed, TTFT percentiles, the
median TTFT of the window's first and last thirds, and the engine's slot
occupancy (mean busy slots per step, and the share of steps with every slot
busy).  Where the last third's median waits much longer than the first's,
the backlog grew: the rate is past the knee.  (A median, so that one burst
of arrivals, which lengthens a few waits, does not read as growth.)  The
last line is the knee: the highest swept rate at which, on every seed, and
at every lower rate, the last third's median TTFT is within ``--growth``
times the first third's.  (``failed`` is not part of the rule: with the
sweep's short drain it counts long requests due near the window's end that
had not finished, which says nothing of a backlog.)  The cell's rate is
then fixed in its workload file below the knee, at four fifths of it where
the tails there spread little enough to bound; the benchmark's runs never
search for it.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[7])
    ap.add_argument("--drain", type=float, default=15.0,
                    help="seconds to follow requests after the window")
    ap.add_argument("--growth", type=float, default=1.5)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()

    import numpy as np

    from bench import harness

    from repro.launch import compile_cache

    compile_cache.enable()
    wl, cfg, tr = harness.cell_files(a.workload)
    runner = harness.load_module("runners", wl["runner"])
    held = {}
    for rate, seed in [(r, s) for r in sorted(a.rates) for s in a.seeds]:
        w = dict(wl, rate_per_s=rate, drain_s=a.drain)
        ctx = harness.Ctx(a.workload, w, cfg, tr, seed, a.seconds, False,
                          time.perf_counter())
        rec = runner.run(ctx)
        ttft = np.asarray(rec["ttft_s"])
        third = max(1, len(ttft) // 3)
        # tokens per observed engine step inside the window: its busy slots
        busy = np.array([n for when, n, _ in rec["steps"] if when < rec["window"][1]])
        row = {
            "rate_per_s": rate, "seed": seed,
            "sent": int(rec["attempted"]), "failed": int(rec["failed"]),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p75_ms": float(np.percentile(ttft, 75)) * 1e3,
            "ttft_p85_ms": float(np.percentile(ttft, 85)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "ttft_first_third_p50_ms": float(np.median(ttft[:third])) * 1e3,
            "ttft_last_third_p50_ms": float(np.median(ttft[-third:])) * 1e3,
            "slots_mean": float(np.mean(busy)),
            "slots_full_share": float(np.mean(busy >= w["engine"]["max_slots"])),
            "itl_p99_ms": float(np.percentile(rec["itl_s"], 99)) * 1e3,
            "decode_step_ms_median": float(np.median(rec["step_ms"])),
            "late_p99_ms": float(np.percentile(rec["late_s"], 99)) * 1e3,
            "checks": {k: v["value"] for k, v in rec["checks"].items()},
        }
        held[rate] = held.get(rate, True) and (
            row["ttft_last_third_p50_ms"] <= a.growth * row["ttft_first_third_p50_ms"])
        emit(row, a.out)
    knee = None
    for rate in sorted(held):
        if not held[rate]:
            break
        knee = rate
    emit({"knee_per_s": knee, "growth": a.growth, "seeds": a.seeds}, a.out)
    return 0


def emit(row: dict, out) -> None:
    print(json.dumps(row), flush=True)
    if out:
        with open(out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    sys.exit(main())
