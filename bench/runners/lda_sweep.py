"""Whole LDA Gibbs sweeps through ``repro.lda.gibbs_step``.

Set-up makes the corpus (``bench/traffic.py``) and the initial state from
the seed, then drives the program's own sweep from that state through its
first ``check_sweeps`` sweeps, which compile it, keeping a host copy of each
state.  The window runs whole sweeps of that same state until ``--seconds``
have passed, each ended by ``block_until_ready``.  After the window the
reference follows the checked sweeps (``bench/refs/<reference>.py``).

The traffic file names the sweep: ``dense``, or ``sparse`` (the MH sweep,
with its ``mh_steps``, ``word_proposal``, ``cap_min`` and ``cap_max``).  The
configuration's ``chunk`` (documents per draw key) is passed to the program
and to the reference alike.  Workload settings: ``check_sweeps``,
``trace_seconds`` (the traced stretch of a ``--trace 1`` run), and
``limits`` (one per compared number).
"""

from __future__ import annotations

import functools
import sys
import time

import numpy as np

from bench import traffic as gen
from bench.harness import load_module, memory_peak_bytes


@functools.lru_cache(maxsize=None)
def _initial_state_fn(M: int, V: int, K: int, maxN: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def init(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        theta = jax.random.dirichlet(k1, jnp.ones((K,)), shape=(M,))
        phi = jax.random.dirichlet(k2, jnp.ones((V,)), shape=(K,)).T
        z = jax.random.randint(k3, (M, maxN), 0, K)
        return theta, phi, z, k4

    return init


def host_state(st) -> dict:
    return {"theta": np.asarray(st.theta), "phi": np.asarray(st.phi),
            "z": np.asarray(st.z), "key": np.asarray(st.key)}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.lda import Corpus, gibbs
    from repro.lda.sparse import SparseSweepCache

    cfg, wl, tr = ctx.config, ctx.workload, ctx.traffic
    M, V, K = cfg["M"], cfg["V"], cfg["K"]
    alpha, beta, chunk = cfg["alpha"], cfg["beta"], int(cfg["chunk"])
    corpus = gen.lda_corpus(tr, M, V, K, ctx.seed)
    pc = Corpus(docs=corpus.docs, lengths=corpus.lengths.astype(np.int32),
                mask=corpus.mask, vocab_size=V)
    theta, phi, z, key = _initial_state_fn(M, V, K, corpus.docs.shape[1])(
        jax.random.PRNGKey(gen.jax_seed(ctx.seed)))
    state = gibbs.LDAState(theta=theta, phi=phi, z=z, key=key, step=jnp.int32(0))
    sparse = {"dense": False, "sparse": True}[tr["sweep"]]
    opts = {}
    if sparse:
        opts = dict(sparse_cache=SparseSweepCache(cap_min=int(tr["cap_min"]),
                                                  cap_max=int(tr["cap_max"])),
                    mh_steps=int(tr["mh_steps"]), word_proposal=tr["word_proposal"])

    def sweep(st):
        with ctx.span("sweep"):
            st = gibbs.gibbs_step(st, pc, alpha=alpha, beta=beta,
                                  method=cfg["sampler_method"], W=cfg["sampler_W"],
                                  chunk=chunk, sparse=sparse, **opts)
            jax.block_until_ready(st)
        return st

    def draw_z(st):
        with ctx.span("draw_z"):
            jax.block_until_ready(gibbs.draw_z(
                st, jnp.asarray(corpus.docs), method=cfg["sampler_method"],
                W=cfg["sampler_W"], chunk=chunk))

    # the checked sweeps: the window's own call on the window's own state
    checked = [host_state(state)]
    for _ in range(int(wl["check_sweeps"])):
        state = sweep(state)
        checked.append(host_state(state))
    if ctx.trace and not sparse:
        draw_z(state)  # the traced run also times the draw program alone

    t_start = ctx.window_start()
    end = t_start + ctx.seconds
    sweeps, traced_sweeps = 0, 0
    if ctx.trace:
        stop = t_start + min(ctx.seconds, float(wl["trace_seconds"]))
        with ctx.traced():
            while time.perf_counter() < stop:
                state = sweep(state)
                sweeps += 1
            traced_sweeps = sweeps
            if not sparse:
                draw_z(state)
    times = []
    while time.perf_counter() < end:
        t = time.perf_counter()
        state = sweep(state)
        times.append(time.perf_counter() - t)
        sweeps += 1
    t_end = time.perf_counter()
    if times:
        q = np.percentile(times, [0, 25, 50, 75, 100])
        print("sweep seconds min/q1/median/q3/max: " + " ".join(f"{x:.4f}" for x in q),
              file=sys.stderr, flush=True)
    peak = memory_peak_bytes()
    del state

    ref = load_module("refs", cfg["reference"])
    docs, mask = jnp.asarray(corpus.docs), jnp.asarray(corpus.mask)
    caps = (ref.sparse_caps([c["z"] for c in checked[:-1]], docs, mask, K, V,
                            int(tr["cap_min"]), int(tr["cap_max"]))
            if sparse else [0] * (len(checked) - 1))
    worst: dict = {}
    for before, after, cap in zip(checked, checked[1:], caps):
        got = ref.compare_sweep(before, after, docs, mask, alpha, beta, chunk, cap=cap,
                                mh_steps=int(tr.get("mh_steps", 0)))
        for k, v in got.items():
            worst[k] = max(worst.get(k, v), v)
    checks = {k: {"value": v, "limit": wl["limits"][k]} for k, v in worst.items()}
    return {
        "attempted": sweeps, "failed": 0, "checks": checks,
        "memory_peak_bytes": peak,
        "tokens": corpus.tokens, "sweeps": sweeps, "traced_sweeps": traced_sweeps,
        "window_s": t_end - t_start,
        "tokens_per_s": sweeps * corpus.tokens / (t_end - t_start),
    }
