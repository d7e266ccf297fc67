"""The benchmark harness: finds a cell's files by name, runs its runner on the
chip, reads its metrics and prints the result line.

Everything that belongs to one cell lives in files of its own, found by the
names in ``BENCHMARK.json``:

  bench/workloads/<cell>.json    configuration, traffic, runner, chips and
                                 the cell's own settings
  bench/configs/<config>.json    the configuration as it is run
  bench/traffic/<traffic>.json   the traffic mix, read by ``bench/traffic.py``
  bench/runners/<runner>.py      ``run(ctx) -> dict``: set-up, window, check
  bench/metrics/<metric>.py      ``read(rec) -> float | None`` for one metric;
                                 a name ``<base>.<qualifier>`` without a file
                                 of its own is read by ``<base>.py``
  bench/refs/<reference>.py      the configuration's plain reference

A new cell, configuration or metric is new files and new entries there.
"""

from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots), loaded
    once per process."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, or the
    shared ``bench/metrics/<base>.py`` of a name ``<base>.<qualifier>`` that
    has no file of its own (one quantity, split by the metric it moves)."""
    if not os.path.isfile(os.path.join(BENCH, "metrics", name + ".py")):
        name = name.split(".")[0]
    return load_module("metrics", name)


def manifest() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell_files(cell: str) -> tuple[dict, dict, dict]:
    """(workload, config, traffic) of a cell."""
    wl = load_json(BENCH, "workloads", cell + ".json")
    cfg = load_json(BENCH, "configs", wl["config"] + ".json")
    tr = load_json(BENCH, "traffic", wl["traffic"] + ".json")
    return wl, cfg, tr


def cell_metrics(man: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of this cell prints: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in man["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved else [])]


class Ctx:
    """What a runner gets: the cell's files, the run's arguments, and the
    clocks, spans and profiler around the measured window."""

    def __init__(self, cell: str, workload: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, t0: float):
        self.cell, self.workload, self.config, self.traffic = cell, workload, config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.t0 = t0
        self.setup_s: Optional[float] = None
        self.trace_file: Optional[str] = None
        self._trace_dir: Optional[str] = None

    def window_start(self) -> float:
        """Mark the end of set-up; returns the window's start (perf_counter)."""
        now = time.perf_counter()
        self.setup_s = now - self.t0
        return now

    @staticmethod
    def span(name: str):
        """A host span, named ``bench.<name>`` in a trace."""
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed stretch when the run is traced (host spans
        only from the benchmark's own annotations, no Python tracer)."""
        if not self.trace:
            yield
            return
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        try:
            with self.span("window"):
                yield
        finally:
            jax.profiler.stop_trace()
            from bench import trace_reduce

            self.trace_file = trace_reduce.find_xplane(self._trace_dir)

    def cleanup(self) -> None:
        if self._trace_dir:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    """The allocator's peak on the fullest chip (0 where not reported)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks or [0]))


def run_cell(cell: str, seed: int, seconds: float, trace: bool, t0: float,
             *, man: Optional[dict] = None, files: Optional[tuple] = None,
             require_chip: bool = True) -> dict:
    """Run one cell and return its result line as a dict."""
    import jax

    man = manifest() if man is None else man
    wl, cfg, tr = cell_files(cell) if files is None else files
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX platform is {devs[0].platform!r}: no TPU")
        if len(devs) < int(wl["chips"]):
            raise NoChip(f"cell needs {wl['chips']} chips, JAX found {len(devs)}")
    ctx = Ctx(cell, wl, cfg, tr, seed, seconds, trace, t0)
    runner = load_module("runners", wl["runner"])
    try:
        rec = runner.run(ctx)
        rec["setup_s"] = ctx.setup_s
        rec["config"] = cfg
        rec["trace"] = None
        if ctx.trace_file:
            from bench import trace_reduce

            rec["trace"] = trace_reduce.load(ctx.trace_file)
            rec["window_ns"] = trace_reduce.window(rec["trace"], "bench.window")
    finally:
        ctx.cleanup()
    from bench import work

    kind = devs[0].device_kind
    rec["peaks"] = work.peaks(kind) if require_chip else None
    metrics = {}
    for m in cell_metrics(man, cell, trace):
        v = metric_reader(m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(rec["memory_peak_bytes"])}
    out = {"correct": all(c["value"] <= c["limit"] for c in rec["checks"].values()),
           "attempted": int(rec["attempted"]), "failed": int(rec["failed"]),
           "metrics": metrics, "device": device}
    if rec["trace"] is not None:
        from bench import trace_reduce

        lo, hi = rec["window_ns"]
        busy, win = trace_reduce.busy_share(rec["trace"], lo, hi)
        device["busy_s"], device["window_s"] = busy, win
        out["breakdown"] = trace_reduce.breakdown(rec["trace"], lo, hi)
    out["checks"] = rec["checks"]
    return out


def print_result(out: dict) -> None:
    """The checks as the last lines of stderr, the result as the last line
    of stdout."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv: List[str], t0: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out = run_cell(a.workload, a.seed, a.seconds, bool(a.trace), t0)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    print_result(out)
    return 0
