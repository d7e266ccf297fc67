#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 5 \
        [--control] [--out readings.jsonl]

Runs the cell once per seed in this one process, through the harness's own
set-up, window and check, and prints each compared number.  With
``--control`` the configuration's control (``bench/controls/<reference>.py``:
the reference in a lower precision, put in the program's place) runs
instead of the program; every limit has to sit below what it reads.  The
benchmark's own runs never run the control.
"""

import argparse
import contextlib
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()

    from bench import harness

    from repro.launch import compile_cache

    compile_cache.enable()
    wl, cfg, tr = harness.cell_files(a.workload)
    patch = contextlib.nullcontext
    if a.control:
        patch = harness.load_module("controls", cfg["reference"]).patch
    for seed in a.seeds:
        t0 = time.perf_counter()
        try:
            with patch():
                out = harness.run_cell(a.workload, seed, a.seconds, False, t0)
            row = {"seed": seed, "control": a.control, "correct": out["correct"],
                   "checks": {k: v["value"] for k, v in out["checks"].items()},
                   "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        except Exception as e:  # a control that crashes has failed; say so
            row = {"seed": seed, "control": a.control, "error": repr(e)}
        print(json.dumps(row), flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
