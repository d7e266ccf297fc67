"""Serving driver: batched request decoding with the butterfly sampler.

    python -m repro.launch.serve --arch qwen3-4b --continuous  # full width, bf16
    python -m repro.launch.serve --arch qwen3-4b --smoke --requests 8
    python -m repro.launch.serve --smoke --dp 2 --tp 2   # sharded decode
    python -m repro.launch.serve --smoke --continuous    # slot-recycled engine

``--dp/--tp`` build a (data, model) mesh (``smallest_fitting_mesh``),
shard the params through the ``repro.dist.sharding`` rules, arm
activation constraints, and run the sampler through the shard_map'd
counter-RNG path (``sampling.plan(mesh=...)``) — tokens are bit-identical
to the unsharded run at a fixed key (DESIGN.md §5).

``--continuous`` serves the same requests through the continuous-batching
engine (``repro.serve.batching``) instead of lockstep ``generate``:
varying prompt/output lengths and heterogeneous per-request sampling
params churn through ``ServeSpec.max_slots`` recycled slots behind ONE
compiled decode step (compile counters are printed as proof).  Composes
with ``--dp/--tp`` (decoder-only archs only).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.dist import sharding as shd
from repro.launch import compile_cache
from repro.launch.mesh import smallest_fitting_mesh
from repro.models import build_model, init_params, logical_axes
from repro.serve.engine import generate


def main():
    """CLI: run a small closed-loop serve session and print stats."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU); default: published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--sampler", default="butterfly",
                    choices=["butterfly", "fenwick", "two_level", "kernel", "prefix", "gumbel"])
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel degree (0 = no mesh, single device)")
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel degree")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(slot recycling, per-request sampling params)")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots for --continuous (0 = ServeSpec default)")
    args = ap.parse_args()

    import dataclasses

    cfg = dataclasses.replace(
        get_config(args.arch, smoke=args.smoke),
        sampler_method=args.sampler, sampler_W=8 if args.smoke else 32,
    )
    model = build_model(cfg)
    # full-width params in bf16: qwen3-4b is ~16 GB in float32, more than
    # one 16 GiB chip holds
    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    params = init_params(jax.random.PRNGKey(0), model.specs, dtype)
    rng = np.random.default_rng(0)
    B = args.requests

    mesh = None
    if args.dp > 0:
        if B % args.dp:
            raise SystemExit(f"--requests {B} must divide by --dp {args.dp}")
        mesh = smallest_fitting_mesh(data=args.dp, model=args.tp)
        params = jax.device_put(
            params, shd.tree_shardings(params, logical_axes(model.specs), mesh)
        )
        shd.set_activation_sharding(mesh)
        print(f"mesh: {dict(mesh.shape)}")

    if args.continuous:
        from repro.serve import ContinuousBatchingEngine, Request, SamplingParams

        mix = (
            SamplingParams(temperature=0.0),
            SamplingParams(temperature=args.temperature, top_k=40),
            SamplingParams(temperature=args.temperature, top_p=0.9),
            SamplingParams(temperature=args.temperature, min_p=0.05),
        )
        reqs = [
            Request(
                prompt=rng.integers(
                    0, cfg.vocab_size, int(rng.integers(1, args.prompt_len + 1))
                ).astype(np.int32),
                max_new_tokens=int(rng.integers(1, args.max_new + 1)),
                seed=i,
                sampling=mix[i % len(mix)],
            )
            for i in range(B)
        ]
        eng = ContinuousBatchingEngine(
            model, params,
            max_slots=args.slots or None,
            max_len=args.prompt_len + args.max_new,
            max_waiting=B, temperature=args.temperature, mesh=mesh,
        )
        eng.warmup(max_prompt_len=args.prompt_len)
        t0 = time.perf_counter()
        done = eng.run(reqs)
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens) for r in done)
        st, cs = eng.stats(), eng.compile_stats()
        print(f"served {len(done)} requests ({toks} tokens) through "
              f"{eng.max_slots} slots in {dt:.2f}s "
              f"({toks / dt:.0f} tok/s, {st['steps']} steps); "
              f"decode-step compiles: {cs['decode_step_compiles']}")
        print(f"first request: {done[0].output_tokens}")
        return

    if cfg.encoder_layers > 0:
        batch = {
            "src_embeds": jnp.array(rng.normal(size=(B, 8, cfg.d_model)), jnp.float32),
            "tgt_tokens": jnp.array(rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32),
        }
    elif cfg.frontend_len > 0:
        batch = {
            "tokens": jnp.array(rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32),
            "frontend_embeds": jnp.array(rng.normal(size=(B, cfg.frontend_len, cfg.d_model)), jnp.float32),
        }
    else:
        batch = {"tokens": jnp.array(rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32)}

    t0 = time.perf_counter()
    res = generate(model, params, batch, max_new_tokens=args.max_new,
                   temperature=args.temperature, key=jax.random.PRNGKey(1),
                   mesh=mesh)
    dt = time.perf_counter() - t0
    print(f"served {B} requests x {res.steps} tokens in {dt:.2f}s "
          f"(sampler={args.sampler}); first request: {res.tokens[0].tolist()}")


if __name__ == "__main__":
    main()
