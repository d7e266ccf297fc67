#!/usr/bin/env python3
"""Bring-up check: LDA Gibbs and qwen3-4b serving on a TPU chip.

    python3 chip_smoke.py              # one chip, full sizes
    python3 chip_smoke.py --chips 4    # only the AD-LDA sweep: 4-chip mesh
                                       # against a one-device mesh
    python3 chip_smoke.py --rehearse   # the same phases on the CPU at
                                       # smoke sizes; never reports ok

One process holds the chip.  Phases, each through the entry points a
user calls:

  lda      the paper's corpus shape (configs/lda.py: M=43556, V=37286,
           K=240, ~3.07M tokens): 3 ``gibbs_step`` sweeps with the
           config's method, 3 with ``method="auto"``; perplexity must be
           finite and fall.
  kernels  the fused butterfly draw (LDA and decode shapes) and the
           factored ``lda_draw`` kernel against their XLA references on
           the same uniforms; at most ``2 + n // 1000`` of n indices may
           differ (float32 reassociation moves a draw that lands on a
           block boundary).
  serve    qwen3-4b at published widths, bf16 weights from PRNGKey(0),
           8 mixed requests through ``ContinuousBatchingEngine``: all
           finish, tokens in range, one decode-step compile, and the
           greedy requests match ``serve.engine.generate``.

Each phase prints its wall time (compile included), resolved sampler
methods, the ``tpu_custom_call`` count of each compiled program and
``peak_bytes_in_use``.  The last stdout line is
``{"ok": true, "device": {...}}`` only when every phase passed on a TPU;
with no TPU, or on any failure, the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))


def _args():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the AD-LDA sweep on a 4-chip mesh")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at smoke sizes (not a chip result)")
    return ap.parse_args()


ARGS = _args()
if ARGS.rehearse:
    # before JAX starts: the CPU backend, with virtual devices for the mesh
    os.environ["JAX_PLATFORMS"] = "cpu"
    if ARGS.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={ARGS.chips}"
        )
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch import compile_cache  # noqa: E402

CACHE_DIR = compile_cache.enable()

from repro import sampling  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.lda import CONFIG as LDA_FULL, SMOKE as LDA_SMOKE  # noqa: E402
from repro.kernels import runtime  # noqa: E402
from repro.lda import gibbs, init_state, synthesize_corpus  # noqa: E402


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def custom_calls(jitted, *args, **kw) -> int:
    """``tpu_custom_call`` ops in the compiled program for these args (a
    later call with the same arguments reuses this compile)."""
    return jitted.lower(*args, **kw).compile().as_text().count("tpu_custom_call")


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B ({peak / 2**30:.2f} GiB)"


def mismatch_limit(n: int) -> int:
    return 2 + n // 1000


def lda_corpus():
    c = LDA_SMOKE if ARGS.rehearse else LDA_FULL
    corpus = synthesize_corpus(seed=0, M=c.M, V=c.V, K=c.K, avg_len=70.5)
    return c, corpus


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_lda(on_chip: bool) -> None:
    c, corpus = lda_corpus()
    M, maxN = corpus.docs.shape
    say("lda", f"corpus M={M} V={c.V} K={c.K} tokens={corpus.total_words} "
               f"maxN={maxN}")
    state = init_state(jax.random.PRNGKey(0), corpus, c.K)
    docs, mask = jnp.asarray(corpus.docs), jnp.asarray(corpus.mask)
    chunk = 256
    p0 = gibbs.perplexity(state, corpus)
    say("lda", f"perplexity at init {p0:.2f}")
    trace = [p0]
    for method, W in ((c.sampler_method, c.sampler_W), ("auto", None)):
        plan = sampling.plan(
            (min(chunk, M) * maxN, c.K), method=method, W=W, dtype="float32",
            has_key=False, factored=True,
        )
        sweep = gibbs._sweep_jit(method, W, chunk, c.K, c.V)
        n_cc = custom_calls(
            sweep, state.theta, state.phi, state.z, state.key, state.step,
            docs, mask, jnp.float32(c.alpha), jnp.float32(c.beta),
        )
        say("lda", f"method={method!r} resolves to {plan.method} W={plan.W}; "
                   f"sweep program tpu_custom_call={n_cc}")
        if on_chip and plan.method in sampling.FACTORED_VARIANTS and n_cc == 0:
            raise RuntimeError(f"{plan.method} sweep holds no Pallas kernel")
        for it in range(3):
            t0 = time.perf_counter()
            state = gibbs.gibbs_step(state, corpus, alpha=c.alpha,
                                     beta=c.beta, method=method, W=W)
            jax.block_until_ready(state.theta)
            dt = time.perf_counter() - t0
            trace.append(gibbs.perplexity(state, corpus))
            say("lda", f"  {method} sweep {it + 1}: perplexity "
                       f"{trace[-1]:.2f} ({dt:.3f} s wall)")
    if not np.all(np.isfinite(trace)):
        raise RuntimeError(f"non-finite perplexity: {trace}")
    if not trace[-1] < trace[0]:
        raise RuntimeError(f"perplexity did not fall: {trace}")


def _count_mismatch(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    n = got.size
    bad = int(np.sum(got != want))
    lim = mismatch_limit(n)
    say("kernels", f"{name}: {bad} of {n} indices differ (limit {lim})")
    if bad > lim:
        raise RuntimeError(f"{name}: {bad} mismatches > {lim}")


def phase_kernels(on_chip: bool) -> None:
    from repro.kernels.butterfly_sample import kernel as bk
    from repro.kernels.butterfly_sample.ref import butterfly_sample_ref
    from repro.kernels.lda_draw import ops as lda_ops

    if on_chip and (runtime.resolve_interpret(None)
                    or lda_ops._resolve_impl(None) != "pallas"):
        raise RuntimeError("kernels would run interpreted or as the XLA twin")
    c, corpus = lda_corpus()
    K, V = c.K, c.V
    rows = min(256, corpus.docs.shape[0])
    B = rows * corpus.docs.shape[1]
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    theta = jax.random.dirichlet(k[0], jnp.ones((K,)), shape=(rows,))
    phi = jax.random.dirichlet(k[1], jnp.ones((V,)), shape=(K,)).T
    doc_ids = jnp.arange(B, dtype=jnp.int32) // corpus.docs.shape[1]
    words = jnp.asarray(corpus.docs[:rows].reshape(-1))
    u = jax.random.uniform(k[2], (B,), jnp.float32)

    # factored lda_draw kernel vs its XLA twin (the W/tb auto picks on v5e)
    W, tb = 16, 16
    n_cc = custom_calls(lda_ops.lda_draw_factored, theta, phi, doc_ids,
                        words, u, W=W, tb=tb, impl="pallas")
    say("kernels", f"lda_draw factored ({B}x{K}, W={W}) "
                   f"tpu_custom_call={n_cc}")
    got = lda_ops.lda_draw_factored(theta, phi, doc_ids, words, u, W=W,
                                    tb=tb, impl="pallas")
    want = lda_ops.lda_draw_factored(theta, phi, doc_ids, words, u, W=W,
                                     tb=tb, impl="xla")
    _count_mismatch("lda_draw kernel vs XLA twin", got, want)

    # fused butterfly draw (method="kernel") at the LDA shape and at the
    # decode shape (8 x vocab: the masked-free two-pass route)
    w_lda = theta[doc_ids] * phi[words]
    vocab = 151936 if not ARGS.rehearse else get_config(
        "qwen3-4b", smoke=True).vocab_size
    logits = 2.0 * jax.random.normal(k[3], (8, vocab), jnp.float32)
    w_dec = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    u_dec = jax.random.uniform(k[4], (8,), jnp.float32)
    for name, w, uu, Wb in (("LDA", w_lda, u, 32), ("decode", w_dec, u_dec, 128)):
        n_cc = custom_calls(bk.butterfly_sample_pallas, w, uu, W=Wb, tb=8)
        say("kernels", f"butterfly kernel {name} {tuple(w.shape)} W={Wb} "
                       f"tpu_custom_call={n_cc}")
        if on_chip and n_cc == 0:
            raise RuntimeError(f"butterfly kernel {name}: no Pallas kernel")
        got = bk.butterfly_sample_pallas(w, uu, W=Wb, tb=8)
        _count_mismatch(f"butterfly kernel vs XLA reference ({name})", got,
                        butterfly_sample_ref(w, uu))


def phase_serve(on_chip: bool) -> None:
    from repro.models import build_model, init_params
    from repro.serve import ContinuousBatchingEngine, Request, SamplingParams
    from repro.serve.engine import generate

    cfg = get_config("qwen3-4b", smoke=ARGS.rehearse)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.bfloat16)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    say("serve", f"{cfg.name}: {n_params} bf16 parameters built in "
                 f"{time.perf_counter() - t0:.1f} s; peak {peak_bytes()}")

    rng = np.random.default_rng(0)
    greedy_len, greedy_new = 24, 12
    mix = [  # (prompt length, new tokens, sampling)
        (greedy_len, greedy_new, SamplingParams(temperature=0.0)),
        (greedy_len, greedy_new, SamplingParams(temperature=0.0)),
        (16, 8, SamplingParams(temperature=0.7, top_k=20, top_p=0.95)),
        (20, 16, SamplingParams(temperature=1.0, min_p=0.05)),
        (31, 10, SamplingParams(temperature=0.9, top_p=0.9)),
        (33, 14, SamplingParams(temperature=0.6, top_k=40)),
        (40, 9, SamplingParams(temperature=1.2)),
        (64, 16, SamplingParams(temperature=0.8, top_k=5, min_p=0.1)),
    ]
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=new, seed=i, sampling=sp)
        for i, (n, new, sp) in enumerate(mix)
    ]
    eng = ContinuousBatchingEngine(model, params)
    S = eng.max_slots
    say("serve", f"{S} slots, max_len {eng.max_len}; decode draw resolves to "
                 f"{eng._plan.method} W={eng._plan.W}")
    n_cc = custom_calls(
        eng._step, eng.params, eng._caches,
        jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.zeros((S, 2), jnp.uint32), jnp.zeros((S,), jnp.uint32),
        jnp.ones((S,), jnp.float32), jnp.zeros((S, 3), jnp.float32),
    )
    say("serve", f"decode step program tpu_custom_call={n_cc}")
    if on_chip and n_cc == 0:
        raise RuntimeError("decode step holds no Pallas kernel")
    t0 = time.perf_counter()
    done = eng.run(reqs)
    dt = time.perf_counter() - t0
    cs = eng.compile_stats()
    toks = sum(len(r.output_tokens) for r in done)
    say("serve", f"served {len(done)} requests, {toks} tokens, "
                 f"{eng.stats()['steps']} decode steps in {dt:.1f} s wall "
                 f"(compile included); decode_step_compiles="
                 f"{cs['decode_step_compiles']} prefill_compiles="
                 f"{cs['prefill_compiles']}")
    for r in done:
        if len(r.output_tokens) != r.max_new_tokens:
            raise RuntimeError(f"request {r.id} unfinished: {r.state}")
        if not all(0 <= t < cfg.vocab_size for t in r.output_tokens):
            raise RuntimeError(f"request {r.id}: token out of range")
    if cs["decode_step_compiles"] != 1:
        raise RuntimeError(f"decode step compiled {cs['decode_step_compiles']}x")

    greedy = [r for r in done if r.sampling.temperature == 0.0]
    res = generate(model, params,
                   {"tokens": jnp.asarray(np.stack([r.prompt for r in greedy]))},
                   max_new_tokens=greedy_new, temperature=0.0)
    for r, ref in zip(greedy, np.asarray(res.tokens).tolist()):
        same = ref == r.output_tokens
        say("serve", f"greedy request {r.id}: engine {r.output_tokens} "
                     f"{'==' if same else '!='} generate {ref}")
        if not same:
            raise RuntimeError(f"greedy request {r.id} differs from generate")


PHASES = {"lda": phase_lda, "kernels": phase_kernels, "serve": phase_serve}


def phase_lda_mesh(on_chip: bool) -> None:
    """One AD-LDA sweep (``make_sharded_gibbs``) on a 4-device data mesh
    against the same sweep on a one-device mesh."""
    from repro.launch.mesh import smallest_fitting_mesh
    from repro.lda.distributed import make_sharded_gibbs

    n = ARGS.chips
    c, corpus = lda_corpus()
    M = corpus.docs.shape[0]
    say("lda-mesh", f"corpus M={M} V={c.V} K={c.K} "
                    f"tokens={corpus.total_words}")
    # one method for both runs: what auto picks per shard on v5e
    method, W = ("lda_kernel", 16)
    out = {}
    for d in (n, 1):
        mesh = smallest_fitting_mesh(data=d)
        place, step = make_sharded_gibbs(mesh, c.K, c.V, alpha=c.alpha,
                                         beta=c.beta, method=method, W=W)
        state = init_state(jax.random.PRNGKey(0), corpus, c.K)
        state, docs, mask = place(state, corpus.docs, corpus.mask)
        n_cc = custom_calls(step, state, docs, mask)
        t0 = time.perf_counter()
        new = step(state, docs, mask)
        jax.block_until_ready(new.theta)
        dt = time.perf_counter() - t0
        devs = len(new.theta.sharding.device_set)
        say("lda-mesh", f"{d}-device mesh: method {method} W={W}, "
                        f"tpu_custom_call={n_cc}, theta on {devs} devices, "
                        f"sweep {dt:.3f} s wall")
        if on_chip and n_cc == 0:
            raise RuntimeError("sharded sweep holds no Pallas kernel")
        if devs != d:
            raise RuntimeError(f"theta spread over {devs} devices, not {d}")
        _, word_topic = gibbs._counts(np.asarray(new.z), corpus.docs,
                                      corpus.mask, c.K, c.V)
        out[d] = (np.asarray(new.z), np.asarray(word_topic),
                  np.asarray(new.phi))
    z_bad = int(np.sum(out[n][0] != out[1][0]))
    wt_bad = int(np.sum(out[n][1] != out[1][1]))
    phi_same = np.array_equal(out[n][2], out[1][2])
    say("lda-mesh", f"z: {z_bad} of {out[1][0].size} differ; word-topic "
                    f"counts: {wt_bad} entries differ; phi bit-identical: "
                    f"{phi_same}")
    if z_bad or wt_bad:
        raise RuntimeError("the mesh sweep differs from the one-device sweep")


def main() -> int:
    dev = jax.devices()[0]
    ndev = len(jax.devices())
    on_chip = dev.platform == "tpu"
    if not on_chip and not ARGS.rehearse:
        print(f"chip_smoke: no TPU found (JAX platform: {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if ndev < ARGS.chips:
        print(f"chip_smoke: --chips {ARGS.chips} needs {ARGS.chips} devices, "
              f"found {ndev}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{ndev}; jax "
          f"{jax.__version__}; compile cache {CACHE_DIR or 'off (CPU)'}", flush=True)
    phases = {"lda-mesh": phase_lda_mesh} if ARGS.chips > 1 else PHASES
    failed = []
    for name, fn in phases.items():
        t0 = time.perf_counter()
        try:
            fn(on_chip)
            status = "PASS"
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            failed.append(name)
            status = "FAIL"
        say(name, f"{status}: {time.perf_counter() - t0:.1f} s wall "
                  f"(compile included); peak_bytes_in_use {peak_bytes()}")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if ARGS.rehearse:
        print("rehearsal passed on the CPU at smoke sizes: not a chip result",
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": ndev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
