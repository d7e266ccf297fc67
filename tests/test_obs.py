"""Program spans and counters (repro.obs): the ring, the compile listener,
the clock shared with the profiler's trace, and the spans the serve engine
and the LDA sweeps open."""

import os
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig, SamplerSpec
from repro.lda import gibbs, synthesize_corpus
from repro.lda.sparse import SparseSweepCache
from repro.models.model import build_model
from repro.models.params import init_params
from repro.serve import (
    ContinuousBatchingEngine, QueueFullError, Request, SamplingParams,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def empty_ring():
    obs.reset()
    yield
    obs.reset()


def _by_id():
    return {s.id: s for s in obs.spans()}


def _under(s, root_ids, by_id):
    """Whether span ``s`` has an ancestor among ``root_ids``."""
    p = s.parent
    while p is not None:
        if p in root_ids:
            return True
        p = by_id[p].parent if p in by_id else None
    return False


# -- the ring ----------------------------------------------------------------


def test_spans_nest_and_record_their_parents():
    with obs.span("outer", k=1) as outer:
        with obs.span("inner") as a:
            pass
        with obs.span("inner") as b:
            with obs.span("leaf") as c:
                pass
    r = obs.record("queued", 5, 9, req=3)
    got = {s.id: s for s in obs.spans()}
    assert got[outer.id].parent is None and got[outer.id].attrs == {"k": 1}
    assert got[a.id].parent == outer.id and got[b.id].parent == outer.id
    assert got[c.id].parent == b.id
    assert r.parent is None and (r.start_ns, r.end_ns, r.attrs) == (5, 9, {"req": 3})
    # closing order; each span lies inside its parent
    assert [s.name for s in obs.spans()] == ["inner", "leaf", "inner", "outer", "queued"]
    for s in (a, b, c):
        p = got[got[s.id].parent]
        assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
    assert [s.id for s in obs.spans("inner")] == [a.id, b.id]
    assert outer.end_ns - outer.start_ns == got[outer.id].end_ns - got[outer.id].start_ns


def test_ring_is_bounded_and_keeps_the_newest():
    n = obs.RING_SIZE + 10
    for i in range(n):
        obs.record("r", i, i + 1, i=i)
    got = obs.spans()
    assert len(got) == obs.RING_SIZE
    assert got[0].attrs["i"] == 10 and got[-1].attrs["i"] == n - 1


def test_counters_count_and_reset():
    assert obs.count("c") == 1
    assert obs.count("c", 4) == 5
    assert obs.counters() == {"c": 5}
    obs.reset()
    assert obs.counters() == {} and obs.spans() == []


def test_threads_keep_their_own_parents_and_lose_no_count():
    workers, rounds = 4 * (os.cpu_count() or 2), 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for _ in range(rounds):
                with obs.span("t.outer", t=i):
                    with obs.span("t.inner", t=i):
                        obs.count("t.n")

        threads = [threading.Thread(target=work, args=(i,)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert obs.counters()["t.n"] == workers * rounds
    by_id = _by_id()
    inner = obs.spans("t.inner")
    assert len(inner) == workers * rounds
    for s in inner:
        p = by_id[s.parent]
        assert p.name == "t.outer" and p.attrs["t"] == s.attrs["t"]


# -- the compile listener ----------------------------------------------------


def test_a_compile_is_recorded_under_the_open_span_once():
    def obs_probe_fn(x):
        return x * 3.0 + 1.0

    f = jax.jit(obs_probe_fn)
    x = jnp.arange(7.0)
    jax.block_until_ready(x)
    with obs.span("first") as first:
        jax.block_until_ready(f(x))
    with obs.span("second") as second:
        jax.block_until_ready(f(x))
    compiles = [s for s in obs.spans("jax.compile") if "obs_probe_fn" in s.attrs["fun_name"]]
    assert len(compiles) == 1
    (c,) = compiles
    assert c.parent == first.id
    assert first.start_ns <= c.start_ns <= c.end_ns <= first.end_ns
    assert not [s for s in obs.spans("jax.compile") if s.parent == second.id]


# -- the clock shared with the trace -----------------------------------------


def test_spans_sit_in_the_profiler_trace_on_the_same_clock():
    sys.path.insert(0, ROOT)
    from bench import trace_reduce

    d = tempfile.mkdtemp(prefix="obs_trace_")
    jax.profiler.start_trace(d)
    try:
        for i in range(5):
            with obs.span("clock.probe", i=i):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    tr = trace_reduce.load(trace_reduce.find_xplane(d), host_prefix="repro.")
    in_trace = sorted(s for n, s, _ in tr.host if n == "repro.clock.probe")
    in_ring = sorted(s.start_ns for s in obs.spans("clock.probe"))
    assert len(in_trace) == len(in_ring) == 5
    a = np.asarray(in_trace) - in_trace[0]
    b = np.asarray(in_ring, float) - in_ring[0]
    assert np.abs(a - b).max() < 1e6  # 1 ms


# -- the serve engine --------------------------------------------------------

CFG = ModelConfig(
    name="tiny-obs", family="dense", num_layers=2, d_model=32,
    num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
    sampler=SamplerSpec(method="fenwick", W=8),
)


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    return model, init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)


def test_engine_spans_per_request_and_per_step(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=3, max_len=40)
    eng.warmup(max_prompt_len=16)
    obs.reset()
    reqs = [
        Request(prompt=np.arange(1, 2 + (5 * i) % 15, dtype=np.int32),
                max_new_tokens=2 + i % 4, seed=i,
                sampling=SamplingParams(temperature=[0.0, 0.8][i % 2], top_k=5))
        for i in range(7)
    ]
    eng.run(reqs)
    by_id = _by_id()

    queue = {s.attrs["req"]: s for s in obs.spans("engine.queue")}
    admit = {s.attrs["req"]: s for s in obs.spans("engine.admit")}
    assert sorted(queue) == sorted(admit) == sorted(r.id for r in reqs)
    assert len(obs.spans("engine.queue")) == len(obs.spans("engine.admit")) == len(reqs)
    for r in reqs:
        q, a = queue[r.id], admit[r.id]
        assert q.start_ns == int(r.arrival_time * 1e9)
        assert q.end_ns <= a.start_ns
        assert a.attrs["prompt"] == r.prompt_len
        assert r.prefill_time == a.end_ns * 1e-9
        assert r.prefill_time <= r.first_token_time

    steps = obs.spans("engine.step")
    assert len(steps) == len(eng.step_times)
    assert [s.attrs["index"] for s in steps] == list(range(len(steps)))
    kids = {}
    for s in obs.spans():
        if s.name.startswith("engine.step."):
            kids.setdefault(s.parent, []).append(s)
    for st, times in zip(steps, eng.step_times):
        ks = kids[st.id]
        assert [k.name for k in ks] == ["engine.step.dispatch", "engine.step.wait",
                                        "engine.step.walk"]
        for k in ks:
            assert st.start_ns <= k.start_ns <= k.end_ns <= st.end_ns
        assert st.attrs["live"] == times["active"] == times["tokens"]
        assert times["dt"] == (ks[1].end_ns - st.start_ns) * 1e-9

    engine_ids = {s.id for s in obs.spans() if s.name.startswith("engine.")}
    assert not [c for c in obs.spans("jax.compile") if _under(c, engine_ids, by_id)]
    c = obs.counters()
    assert c["engine.admitted"] == len(reqs)
    assert c["engine.tokens"] == sum(len(r.output_tokens) for r in reqs)


def test_engine_counts_rejections(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=1, max_len=16,
                                   max_waiting=1)
    with pytest.raises(ValueError):
        eng.submit_nowait(Request(prompt=np.ones(10, np.int32), max_new_tokens=10))
    eng.submit_nowait(Request(prompt=np.ones(3, np.int32), max_new_tokens=2))
    with pytest.raises(QueueFullError):
        eng.submit_nowait(Request(prompt=np.ones(3, np.int32), max_new_tokens=2))
    assert obs.counters()["engine.rejected"] == 2


@pytest.mark.parametrize("act_mesh", [False, True], ids=["plain", "act_mesh"])
def test_engine_counts_each_in_place_cache_write(model_and_params, act_mesh):
    """``engine.cache_inplace`` counts one per decode step that wrote only
    its new K/V rows: every step of a plain engine, none under an
    activation mesh, where the layers rewrite their (possibly
    sequence-sharded) caches instead."""
    from jax.sharding import Mesh

    from repro.dist import sharding as shd

    model, params = model_and_params
    if act_mesh:
        shd.set_activation_sharding(
            Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model")))
    try:
        eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=24)
        eng.run([Request(prompt=np.arange(1, 2 + i, dtype=np.int32),
                         max_new_tokens=3 + i, seed=i) for i in range(3)])
    finally:
        shd.set_activation_sharding(None)
    steps = eng.stats()["steps"]
    assert steps == len(obs.spans("engine.step")) > 0
    assert obs.counters().get("engine.cache_inplace", 0) == (0 if act_mesh else steps)


def test_a_compile_in_an_engine_step_is_seen():
    """The counterpart of the zero-recompile check: a step's first call
    compiles inside its dispatch span, and the record shows it."""
    model = build_model(CFG)
    params = init_params(jax.random.PRNGKey(1), model.specs, jnp.float32)
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=16)
    eng.run([Request(prompt=np.ones(3, np.int32), max_new_tokens=2)])
    by_id = _by_id()
    (first,) = [s for s in obs.spans("engine.step") if s.attrs["index"] == 0]
    under = [c for c in obs.spans("jax.compile") if _under(c, {first.id}, by_id)]
    assert any("step" in c.attrs["fun_name"] for c in under)
    assert all(by_id[c.parent].name == "engine.step.dispatch" for c in under)


# -- LDA sweeps --------------------------------------------------------------


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_lda_sweep_spans(sparse):
    corpus = synthesize_corpus(3, M=24, V=48, K=8, avg_len=12, max_len=24)
    state = gibbs.init_state(jax.random.PRNGKey(0), corpus, 8)
    opts = dict(sparse=True, sparse_cache=SparseSweepCache()) if sparse else {}
    for _ in range(2):
        state = gibbs.gibbs_step(state, corpus, method="butterfly", W=8, chunk=8, **opts)
    jax.block_until_ready(state)
    sweeps = obs.spans("lda.sweep")
    assert len(sweeps) == 2
    assert sweeps[1].attrs["index"] == sweeps[0].attrs["index"] + 1
    for sw in sweeps:
        kids = [s for s in obs.spans() if s.parent == sw.id]
        names = {k.name for k in kids}
        assert {"lda.upload", "lda.dispatch"} <= names
        assert ("lda.sync" in names) == sparse
        assert names <= {"lda.upload", "lda.dispatch", "lda.sync", "jax.compile"}
        assert [k.name for k in kids if k.name != "jax.compile"][0] == "lda.upload"
        for k in kids:
            assert sw.start_ns <= k.start_ns <= k.end_ns <= sw.end_ns
    assert obs.counters()["lda.sweeps"] == 2


def test_lda_draw_z_span():
    corpus = synthesize_corpus(4, M=16, V=32, K=4, avg_len=8, max_len=16)
    state = gibbs.init_state(jax.random.PRNGKey(0), corpus, 4)
    docs = jnp.asarray(corpus.docs)
    z = gibbs.draw_z(state, docs, method="butterfly", W=4, chunk=8)
    assert z.shape == corpus.docs.shape
    (d,) = obs.spans("lda.draw_z")
    assert d.parent is None and not obs.spans("lda.sweep")
