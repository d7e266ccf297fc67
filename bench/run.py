#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (loading, data, weights, warm-up and any compile) is timed from the
start of this process to the start of the measured window.  JAX's persistent
compilation cache lives in ``.jax_cache/`` at the root of the checkout, so
only a cell's first run in a checkout compiles.  With no TPU, or fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# before JAX starts: the cache directory is fixed inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
