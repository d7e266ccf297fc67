"""Open-loop serving benchmark for the continuous-batching engine.

Drives :class:`repro.serve.ContinuousBatchingEngine` with Poisson
arrivals (open loop: the arrival process never waits for the system, so
queueing shows up as latency instead of being hidden by a closed loop's
back-pressure), heterogeneous per-request sampling params, and varying
prompt/output lengths — the workload the engine's zero-retrace design
exists for.

Reports requests/sec and tokens/sec of goodput, p50/p99 time-to-first-
token, per-token (inter-token gap) and end-to-end latency, admission
rejections, and the engine's compile counters (the decode step must
compile exactly once; the run *fails* if churn retraced it).

Writes ``BENCH_serve.json``: a ``records`` list in the shape
``benchmarks/check_regression.py`` gates (rows keyed
``(method, B, K, W, devices)`` with median ``us`` — ``serve_step`` is
the per-decode-step wall time, ``serve_prefill`` the per-prefill wall
time) plus a human-facing ``summary``.  CI runs ``--smoke`` and diffs
against the committed baseline::

    python benchmarks/serve_bench.py --smoke --json fresh/BENCH_serve.json
    python benchmarks/check_regression.py BENCH_serve.json \\
        fresh/BENCH_serve.json --threshold 1.6
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig, SamplerSpec, ServeSpec
from repro.models.model import build_model
from repro.models.params import init_params
from repro.serve import (
    ContinuousBatchingEngine,
    QueueFullError,
    Request,
    SamplingParams,
)

SCHEMA = "repro-serve-bench-v1"

# the benchmark model: tiny enough that CPU CI finishes in seconds, big
# enough that the decode step dominates the asyncio machinery
BENCH_CFG = ModelConfig(
    name="serve-bench-tiny", family="dense", num_layers=2, d_model=64,
    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=512,
    sampler=SamplerSpec(method="butterfly", W=32),
    serve=ServeSpec(max_slots=8, max_waiting=64, max_len=128, prefill_chunk=2),
)

# heterogeneous per-request sampling mix (cycled by request index):
# greedy, top-k, nucleus, temperature-only — one compiled step serves all
PARAM_MIX = (
    SamplingParams(temperature=0.0),
    SamplingParams(temperature=0.8, top_k=40),
    SamplingParams(temperature=1.0, top_p=0.9),
    SamplingParams(temperature=1.2, min_p=0.05),
)


def make_requests(n: int, rate: float, max_len: int, seed: int = 0):
    """n requests with Poisson arrival offsets (exponential inter-arrival
    at ``rate`` req/s) and varying prompt/output lengths."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    reqs = []
    for i in range(n):
        plen = int(rng.integers(1, max(2, max_len // 4)))
        max_new = int(rng.integers(4, max(5, max_len // 4)))
        reqs.append(
            Request(
                prompt=rng.integers(0, BENCH_CFG.vocab_size, plen).astype(np.int32),
                max_new_tokens=max_new,
                seed=i,
                sampling=PARAM_MIX[i % len(PARAM_MIX)],
            )
        )
    return reqs, arrivals


async def drive(engine: ContinuousBatchingEngine, reqs, arrivals):
    """Open-loop: submit request i at its arrival offset regardless of
    system state; count admission rejections instead of retrying."""
    await engine.start()
    t0 = time.perf_counter()
    admitted, rejected = [], 0
    for req, at in zip(reqs, arrivals):
        delay = at - (time.perf_counter() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        try:
            admitted.append(await engine.submit(req))
        except (QueueFullError, ValueError):
            rejected += 1
    done = await asyncio.gather(*(r.future for r in admitted))
    await engine.stop()
    wall = time.perf_counter() - t0
    return list(done), rejected, wall


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else float("nan")


def summarize(done, rejected, wall, engine):
    ttft = [r.ttft for r in done if r.ttft == r.ttft]
    e2e = [r.e2e_latency for r in done]
    gaps = []
    for r in done:
        ts = r.token_times
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    tokens = sum(len(r.output_tokens) for r in done)
    return {
        "requests": len(done),
        "rejected": rejected,
        "wall_s": wall,
        "requests_per_s": len(done) / wall if wall else float("nan"),
        "tokens_out": tokens,
        "tokens_per_s": tokens / wall if wall else float("nan"),
        "ttft_p50_ms": _pct(ttft, 50) * 1e3,
        "ttft_p99_ms": _pct(ttft, 99) * 1e3,
        "token_p50_ms": _pct(gaps, 50) * 1e3,
        "token_p99_ms": _pct(gaps, 99) * 1e3,
        "e2e_p50_ms": _pct(e2e, 50) * 1e3,
        "e2e_p99_ms": _pct(e2e, 99) * 1e3,
        "compile": engine.compile_stats(),
        "engine": engine.stats(),
    }


def records_from(engine, summary, since_ns: int):
    """check_regression-gated rows: median per-decode-step and per-prefill
    wall time under the open-loop load.  A prefill's time is its
    ``engine.admit`` span (prefill and insert, up to the host sync), of the
    admissions that started at or after ``since_ns``."""
    B = engine.max_slots
    K = engine.model.cfg.padded_vocab
    recs = [
        {
            "method": "serve_step", "B": B, "K": K, "W": 0, "devices": 1,
            "us": s["dt"] * 1e6, "active": s["active"],
        }
        for s in engine.step_times
    ]
    recs += [
        {
            "method": "serve_prefill", "B": B, "K": K, "W": 0, "devices": 1,
            "us": (a.end_ns - a.start_ns) * 1e-3, "bucket": a.attrs["bucket"],
        }
        for a in obs.spans("engine.admit") if a.start_ns >= since_ns
    ]
    return recs


def run(n_requests=64, rate=200.0, slots=8, max_len=128, seed=0):
    model = build_model(BENCH_CFG)
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)
    engine = ContinuousBatchingEngine(
        model, params, max_slots=slots, max_len=max_len,
        max_waiting=max(16, n_requests), eos_id=None,
    )
    engine.warmup(max_prompt_len=max(2, max_len // 4))
    post_warmup = engine.compile_stats()["decode_step_compiles"]

    reqs, arrivals = make_requests(n_requests, rate, max_len, seed=seed)
    start_ns = time.perf_counter_ns()
    done, rejected, wall = asyncio.run(drive(engine, reqs, arrivals))

    summary = summarize(done, rejected, wall, engine)
    compiles = summary["compile"]["decode_step_compiles"]
    if compiles != post_warmup:
        raise SystemExit(
            f"decode step retraced under churn: {post_warmup} -> {compiles} "
            "compiles (the zero-retrace invariant is broken)"
        )
    return engine, summary, start_ns


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate, req/s (open loop)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (24 requests, small budget)")
    ap.add_argument("--json", default="BENCH_serve.json", metavar="PATH")
    ap.add_argument("--no-json", action="store_true")
    args = ap.parse_args(argv)

    if args.smoke:
        args.requests = min(args.requests, 24)
        args.max_len = min(args.max_len, 64)

    engine, summary, start_ns = run(
        n_requests=args.requests, rate=args.rate, slots=args.slots,
        max_len=args.max_len, seed=args.seed,
    )

    print(f"requests/s   {summary['requests_per_s']:9.1f}   "
          f"(done {summary['requests']}, rejected {summary['rejected']})")
    print(f"tokens/s     {summary['tokens_per_s']:9.1f}   "
          f"({summary['tokens_out']} tokens in {summary['wall_s']:.2f}s)")
    print(f"TTFT   p50 {summary['ttft_p50_ms']:8.2f} ms   "
          f"p99 {summary['ttft_p99_ms']:8.2f} ms")
    print(f"token  p50 {summary['token_p50_ms']:8.2f} ms   "
          f"p99 {summary['token_p99_ms']:8.2f} ms")
    print(f"e2e    p50 {summary['e2e_p50_ms']:8.2f} ms   "
          f"p99 {summary['e2e_p99_ms']:8.2f} ms")
    print(f"decode-step compiles: "
          f"{summary['compile']['decode_step_compiles']} (zero retraces)")

    if not args.no_json:
        blob = {
            "schema": SCHEMA,
            "backend": jax.default_backend(),
            "config": {
                "requests": args.requests, "rate": args.rate,
                "slots": args.slots, "max_len": args.max_len,
                "model": BENCH_CFG.name, "vocab": BENCH_CFG.padded_vocab,
            },
            "records": records_from(engine, summary, start_ns),
            "summary": summary,
        }
        with open(args.json, "w") as f:
            json.dump(blob, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    raise SystemExit(main())
