"""Production training driver.

    python -m repro.launch.train --arch llama3-8b --steps 200 \
        --ckpt-dir /tmp/ckpt --smoke            # CPU-sized model
    python -m repro.launch.train --app lda      # the paper's application
    python -m repro.launch.train --coordinator 127.0.0.1:8765 ...
                                                # one of N processes

Wires together: config registry -> model -> sharding rules -> optimizer ->
fault-tolerant checkpoint loop (async save, preemption hook, straggler
monitor, deterministic pipeline cursor).  Multi-process runs bring up
``jax.distributed`` through :func:`repro.dist.multihost.init_from_env`
(``--coordinator`` or the ``REPRO_COORDINATOR``/``REPRO_NUM_PROCESSES``/
``REPRO_PROCESS_ID`` env contract); every process runs this same loop,
writes its own checkpoint shards, and beats its own heartbeat mailbox —
process 0 additionally polls the mailboxes to drive the
:class:`~repro.dist.monitor.StepMonitor`.  With no coordinator
configured the identical code runs single-process on the local mesh.
See docs/OPERATIONS.md for the runbook.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import TokenPipeline
from repro.dist import multihost
from repro.dist import sharding as shd
from repro.dist.fault import CheckpointManager, install_preemption_handler, preempted
from repro.dist.heartbeat import MonitorFeeder, open_mailbox
from repro.dist.monitor import StepMonitor
from repro.launch import compile_cache


def train_lm(args):
    """The LM training loop: build, place, restore-if-possible, step.

    In a multi-process run every process executes this identical loop;
    collective compute, per-host checkpoint shards and heartbeat
    mailboxes keep them coherent without any host-specific branches
    beyond "process 0 prints and polls the monitor".
    """
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model, init_params, logical_axes
    from repro.train.optimizer import make_optimizer
    from repro.train.train_step import make_train_step

    info = multihost.init_from_env(coordinator=args.coordinator or None)
    is_lead = info.process_index == 0

    def say(*a):
        """Print from process 0 only (every process runs this loop)."""
        if is_lead:
            print(*a)

    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeConfig("cli", seq_len=args.seq_len, global_batch=args.batch, kind="train")
    model = build_model(cfg)
    mesh = make_host_mesh(model=args.tp)
    params = init_params(jax.random.PRNGKey(args.seed), model.specs, jnp.float32)
    opt = make_optimizer(args.optimizer, lr=args.lr, warmup=args.warmup,
                         total_steps=args.steps)
    opt_state = opt.init(params)

    # Place params/optimizer state through the rules engine; the same
    # sharding trees make restore *elastic* — a checkpoint from any other
    # mesh lands on this one (repro.dist.fault).
    param_axes = logical_axes(model.specs)
    param_sh = shd.tree_shardings(params, param_axes, mesh)
    opt_sh = shd.tree_shardings(
        opt_state, shd.optimizer_state_axes(args.optimizer, param_axes), mesh
    )
    params = jax.device_put(params, param_sh)
    opt_state = jax.device_put(opt_state, opt_sh)
    shd.set_activation_sharding(mesh if len(jax.devices()) > 1 else None)

    pipe = TokenPipeline(cfg, shape, seed=args.seed)
    step_fn = jax.jit(make_train_step(model, opt, remat=args.remat))

    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    monitor = StepMonitor(num_hosts=info.process_count,
                          heartbeat_timeout=args.heartbeat_timeout)
    # heartbeats go through shared storage only when the run is actually
    # multi-process; otherwise the in-process mailbox (same code path)
    hb_dir = args.heartbeat_dir or (
        os.path.join(args.ckpt_dir, "heartbeats")
        if args.ckpt_dir and info.is_multiprocess else ""
    )
    mailbox = open_mailbox(hb_dir or None, host=info.process_index)
    feeder = MonitorFeeder(monitor, mailbox) if is_lead else None
    install_preemption_handler()

    start = 0
    if mgr and mgr.latest_step() is not None:
        (restored, extra) = mgr.restore(
            like={"params": params, "opt": opt_state},
            shardings={"params": param_sh, "opt": opt_sh},
        )
        params, opt_state = restored["params"], restored["opt"]
        pipe.restore(extra["cursor"])
        start = extra["step"]
        say(f"resumed from step {start}")

    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        t0 = time.perf_counter()
        params, opt_state, m = step_fn(params, opt_state, batch, jnp.int32(step))
        jax.block_until_ready(m.loss)
        dt = time.perf_counter() - t0
        mailbox.beat(step=step, step_time=dt, tokens=float(m.tokens))
        if feeder is not None:
            feeder.poll(now=time.time())
            dead = monitor.dead_hosts(now=time.time())
            if dead:
                say(f"WARNING: hosts {dead} missed heartbeats for "
                    f">{monitor.heartbeat_timeout:.0f}s")
        if step % args.log_every == 0:
            say(f"step {step:5d} loss {float(m.loss):.4f} ce {float(m.ce):.4f} "
                f"gnorm {float(m.grad_norm):.2f} {dt*1e3:.0f}ms "
                f"({float(m.tokens)/dt:.0f} tok/s)")
        save_now = mgr and (step % args.ckpt_every == 0 and step > start)
        if mgr and (save_now or preempted()):
            mgr.save(step + 1, {"params": params, "opt": opt_state},
                     extra={"cursor": pipe.cursor(), "step": step + 1},
                     mesh=mesh)
            if preempted():
                mgr.wait()
                say(f"preempted; checkpoint committed at step {step + 1}")
                return
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state},
                 extra={"cursor": pipe.cursor(), "step": args.steps},
                 block=True, mesh=mesh)
    summary = monitor.summary()
    if args.monitor_out and is_lead:
        import json

        with open(args.monitor_out, "w") as f:
            json.dump({"summary": summary, "hosts": monitor.summary_rows()}, f,
                      indent=2)
        say(f"monitor summary written to {args.monitor_out}")
    say("training complete;", summary)


def train_lda(args):
    """The LDA Gibbs loop (the paper's application) on synthetic corpora."""
    from repro.configs.lda import SMOKE as LDA_SMOKE, CONFIG as LDA_FULL
    from repro.lda import gibbs_step, init_state, perplexity, synthesize_corpus

    c = LDA_SMOKE if args.smoke else LDA_FULL
    scale = 1.0 if not args.smoke else None
    corpus = synthesize_corpus(seed=args.seed, M=c.M, V=c.V, K=c.K, avg_len=70.5)
    state = init_state(jax.random.PRNGKey(args.seed), corpus, c.K)
    for it in range(args.steps):
        t0 = time.perf_counter()
        state = gibbs_step(state, corpus, alpha=c.alpha, beta=c.beta,
                           method=c.sampler_method, W=c.sampler_W)
        jax.block_until_ready(state.theta)
        if it % args.log_every == 0:
            print(f"iter {it:4d} perplexity {perplexity(state, corpus):.1f} "
                  f"{(time.perf_counter()-t0)*1e3:.0f}ms")
    print("gibbs complete")


def main():
    """CLI entry point: parse flags, dispatch to the LM or LDA loop."""
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="lm", choices=["lm", "lda"])
    ap.add_argument("--arch", default="llama3-8b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adamw8bit", "adafactor"])
    ap.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (mesh = (devices/tp, tp))")
    ap.add_argument("--monitor-out", default="",
                    help="write the StepMonitor summary JSON here (CI artifact)")
    ap.add_argument("--coordinator", default="",
                    help="host:port of process 0's jax.distributed coordinator "
                         "(or set REPRO_COORDINATOR; empty = single-process)")
    ap.add_argument("--heartbeat-dir", default="",
                    help="shared mailbox dir for cross-host heartbeats "
                         "(default: <ckpt-dir>/heartbeats in multi-process runs)")
    ap.add_argument("--heartbeat-timeout", type=float, default=60.0,
                    help="seconds without a heartbeat before a host is "
                         "declared dead")
    args = ap.parse_args()
    if args.app == "lda":
        train_lda(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
