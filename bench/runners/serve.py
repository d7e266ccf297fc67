"""Serving a decoder through ``repro.serve.ContinuousBatchingEngine``.

Set-up builds the model from the configuration file, makes the weights on
the device in one jitted call from the seed, starts the engine with the
cell's settings and warms up exactly the prefill buckets the traffic can
reach, and the decode step.  The window then drives the engine's public
asyncio surface (``start``, ``submit``, ``stop``):

  poisson  requests are submitted at their due times for ``--seconds``, and
           followed to completion after the window (at most ``drain_s``);
           a request refused, or unfinished then, has failed.
  backlog  every request is due at 0 and the window is cut at ``--seconds``.

A coroutine of the benchmark's own stamps each request's tokens with the
host clock each time control returns from the engine's step, so latencies
are the benchmark's and start from the due time.  After the window the
reference (``bench/refs/<reference>.py``) runs over a sample, drawn from the
seed, of the finished requests with the longest among them.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import gc
import time

import numpy as np

from bench import traffic as gen
from bench.harness import load_module, memory_peak_bytes


def program_config(cfg: dict):
    """The program's model config for ``program_arch``, with every size from
    the configuration file (Hugging Face key names); what the file does not
    state, such as the block's q/k norm, stays as the program's config has
    it."""
    from repro.configs import get_config

    base = get_config(cfg["program_arch"])
    return dataclasses.replace(
        base, name=cfg["name"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], act=cfg["hidden_act"])


@functools.lru_cache(maxsize=None)
def _param_maker(leaves: tuple, dtype: str, std: float):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, init) in zip(keys, leaves):
            if init == "ones":
                out.append(jnp.ones(shape, dt))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dt))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32) * std).astype(dt))
        return out

    return build


def make_params(specs, seed: int, dtype: str, std: float):
    """The served weights, in the type they are served in, made on the
    device in one jitted call: norm scales one, matrices normal(0, std)."""
    import jax

    from repro.models.params import is_spec

    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    shapes = tuple((tuple(s.shape), s.init) for s in leaves)
    arrs = _param_maker(shapes, dtype, std)(jax.random.PRNGKey(gen.jax_seed(seed, 1)))
    return jax.tree.unflatten(treedef, arrs)


def _request(r: gen.ServeRequest):
    from repro.serve import Request, SamplingParams

    if r.temperature == 0.0:
        sp = SamplingParams(temperature=0.0)
    else:
        sp = SamplingParams(temperature=r.temperature, top_k=r.top_k,
                            top_p=r.top_p, min_p=r.min_p)
    return Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                   sampling=sp, seed=r.seed)


@dataclasses.dataclass
class Tracked:
    spec: gen.ServeRequest
    req: object = None
    due: float = 0.0            # perf_counter
    sent: float = -1.0
    refused: bool = False
    stamps: list = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.req is not None and self.req.done and not self.refused \
            and len(self.stamps) == len(self.req.output_tokens) > 0


def run(ctx) -> dict:
    from repro.models import build_model
    from repro.serve import ContinuousBatchingEngine, QueueFullError

    cfg, wl, tr = ctx.config, ctx.workload, ctx.traffic
    model = build_model(program_config(cfg))
    params = make_params(model.specs, ctx.seed, cfg["torch_dtype"],
                         cfg["initializer_range"])
    eng = ContinuousBatchingEngine(model, params, **wl["engine"])
    specs = gen.serve_requests(tr, ctx.seed, cfg["vocab_size"], ctx.seconds,
                               rate_per_s=wl.get("rate_per_s"), count=wl.get("backlog"))
    sp = tr["sampling"]
    warm = [gen.ServeRequest(index=i, due_s=0.0, prompt=np.zeros(n, np.int32),
                             max_new_tokens=2, temperature=float(sp["temperature"]),
                             top_k=int(sp["top_k"]), top_p=float(sp["top_p"]),
                             min_p=float(sp["min_p"]), seed=i)
            for i, n in enumerate(gen.prefill_buckets(tr))]
    with ctx.span("warmup"):
        eng.run([_request(r) for r in warm])
    eng.reset_metrics()

    tracked = [Tracked(spec=s) for s in specs]
    steps = []          # (time, tokens, attended positions) per observed step
    trace_t = []
    backlog = tr["process"] == "backlog"

    async def window():
        await eng.start()
        t0 = ctx.window_start()
        end = t0 + ctx.seconds
        stop = {"now": False}
        for t in tracked:
            t.due = t0 + t.spec.due_s

        def observe():
            now = time.perf_counter()
            n_tok = attended = 0
            for t in tracked:
                if t.req is None or t.refused:
                    continue
                n = len(t.req.output_tokens)
                while len(t.stamps) < n:
                    t.stamps.append(now)
                    n_tok += 1
                    attended += t.spec.prompt.size + len(t.stamps) - 1
            if n_tok:
                steps.append((now, n_tok, attended))

        async def observer():
            while not stop["now"]:
                observe()
                await asyncio.sleep(0)

        async def generator():
            for t in tracked:
                delay = t.due - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                if time.perf_counter() >= end:
                    break
                t.req = _request(t.spec)
                t.sent = time.perf_counter()
                try:
                    with ctx.span("submit"):
                        await eng.submit(t.req)
                except (QueueFullError, ValueError):
                    t.refused = True

        async def tracer():
            span = min(ctx.seconds, float(wl["trace_seconds"]))
            await asyncio.sleep(max(0.0, (ctx.seconds - span) / 2))
            with ctx.traced():
                trace_t.append(time.perf_counter())
                await asyncio.sleep(span)
                trace_t.append(time.perf_counter())

        obs = asyncio.create_task(observer())
        tasks = [asyncio.create_task(generator())]
        if ctx.trace:
            tasks.append(asyncio.create_task(tracer()))
        await asyncio.sleep(max(0.0, end - time.perf_counter()))
        if not backlog:
            limit = end + float(wl["drain_s"])
            while time.perf_counter() < limit and not all(
                    t.req is None or t.refused or t.req.done for t in tracked):
                await asyncio.sleep(0.01)
        await asyncio.gather(*tasks)
        await eng.stop()
        observe()
        stop["now"] = True
        await obs
        return t0, end

    t0, end = asyncio.run(window())
    peak = memory_peak_bytes()
    sent = [t for t in tracked if t.req is not None]
    step_ms = [1e3 * s["dt"] for s in eng.step_times]
    del eng
    gc.collect()

    ref = load_module("refs", cfg["reference"])
    seqs = pick_checked(tracked, ctx.seed, int(wl["check_requests"]))
    checks = ref.compare(params, cfg, seqs, pad_to=int(wl["engine"]["max_len"]),
                         top_k=int(tr["sampling"]["top_k"]),
                         control=getattr(ref, "CONTROL", False))
    checks = {k: {"value": v, "limit": wl["limits"][k]} for k, v in checks.items()}

    rec = {"checks": checks, "memory_peak_bytes": peak, "window": (t0, end),
           "steps": steps, "step_ms": step_ms, "trace_t": tuple(trace_t),
           "engine": wl["engine"]}
    if backlog:
        rec["attempted"] = len(sent)
        rec["failed"] = sum(t.refused for t in sent)
        rec["output_tokens"] = sum(1 for t in sent for s in t.stamps if s < end)
    else:
        failed = [t for t in sent if not t.finished]
        rec["attempted"], rec["failed"] = len(sent), len(failed)
        rec["ttft_s"] = [t.stamps[0] - t.due if t.finished else float("inf") for t in sent]
        rec["itl_s"] = [b - a for t in sent for a, b in zip(t.stamps, t.stamps[1:])]
        rec["late_s"] = [t.sent - t.due for t in sent]
        rec["prefill_tokens"] = [(t.stamps[0], t.spec.prompt.size - 1)
                                 for t in sent if t.stamps]
    return rec


def pick_checked(tracked, seed: int, n: int):
    """Up to n finished requests: the longest, then a draw from the seed that
    alternates greedy and sampled ones.  (prompt, tokens, greedy) each."""
    done = [t for t in tracked if t.finished]
    if not done:
        raise RuntimeError("no request finished: nothing to check")
    done.sort(key=lambda t: t.spec.index)
    longest = max(done, key=lambda t: t.spec.prompt.size + len(t.req.output_tokens))
    rest = [t for t in done if t is not longest]
    order = gen.rng(seed, 20).permutation(len(rest))
    greedy = [rest[i] for i in order if rest[i].spec.temperature == 0.0]
    sampled = [rest[i] for i in order if rest[i].spec.temperature != 0.0]
    pick = [longest]
    while len(pick) < n and (greedy or sampled):
        want_greedy = sum(t.spec.temperature == 0.0 for t in pick) * 2 < len(pick) + 1
        src = greedy if (want_greedy and greedy) or not sampled else sampled
        pick.append(src.pop(0))
    return [(t.spec.prompt, np.asarray(t.req.output_tokens, np.int32),
             t.spec.temperature == 0.0) for t in pick]
