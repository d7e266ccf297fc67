"""The trace reduction, on synthetic traces and on one recorded on the CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"


def synthetic():
    # ns: a while (0-100) holding two ops, a gap 100-150, one op 150-180,
    # a gap to 200; host spans around them
    ops = {DEV: [
        ("%while.1 = loop", 0.0, 100.0),
        ("%fusion.2 = body", 10.0, 30.0),
        ("%custom-call.3 = _walk_kernel", 50.0, 20.0),
        ("%fusion.4 = tail", 150.0, 30.0),
    ]}
    modules = {DEV: [("jit_step(11)", 0.0, 100.0), ("jit__lambda(12)", 150.0, 30.0)]}
    host = [("bench.window", 0.0, 200.0), ("bench.sweep", 0.0, 110.0),
            ("bench.draw_z", 140.0, 60.0)]
    return tr.Trace(ops=ops, modules=modules, host=host)


def test_union_gaps_and_busy_share():
    t = synthetic()
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    busy, win = tr.busy_share(t, *tr.window(t, "bench.window"))
    assert busy == pytest.approx(130e-9)
    assert win == pytest.approx(200e-9)
    assert tr.gaps([(0, 100), (150, 180)], 0, 200) == [(100, 150), (180, 200)]


def test_self_times_subtract_nested_ops():
    st = tr.self_times(synthetic().ops[DEV])
    assert st["%while.1 = loop"] == pytest.approx(50.0)
    assert st["%fusion.2 = body"] == pytest.approx(30.0)


def test_module_and_op_time():
    t = synthetic()
    assert tr.module_time(t, ("jit_step",), 0, 200) == (pytest.approx(100e-9), 1)
    assert tr.module_time(t, ("jit__lambda",), 0, 140) == (0.0, 0)
    assert tr.op_time(t, ("_walk_kernel",), 0, 200) == (pytest.approx(20e-9), 1)


def test_device_time_inside_host_spans():
    t = synthetic()
    sweep = tr.span_intervals(t, "bench.sweep")
    assert tr.device_time_in(t, sweep) == pytest.approx(100e-9)
    draw = tr.span_intervals(t, "bench.draw_z")
    assert tr.device_time_in(t, draw) == pytest.approx(30e-9)


def test_breakdown_names_gaps_by_innermost_host_span():
    bd = tr.breakdown(synthetic(), 0, 200)
    assert bd["device_ops"][0] == ["%while.1 = loop", pytest.approx(50e-9)]
    # the 100-150 gap's middle (125) is inside only bench.window; the
    # 180-200 gap's middle lies inside bench.draw_z, the shorter span
    assert bd["idle_gaps"] == [["bench.window", pytest.approx(50e-9)],
                               ["bench.draw_z", pytest.approx(20e-9)]]


def test_no_device_plane_reads_no_busy_time():
    t = tr.Trace(ops={}, modules={}, host=[("bench.window", 0.0, 10.0)])
    assert tr.busy_share(t, 0, 10) == (0.0, pytest.approx(10e-9))
    assert tr.breakdown(t, 0, 10) == {"device_ops": [], "idle_gaps": []}


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
    path = tr.find_xplane(str(tmp_path))
    assert path is not None
    t = tr.load(path)
    lo, hi = tr.window(t, "bench.window")
    assert hi > lo
    assert all(name.startswith("bench.") for name, _, _ in t.host)


def recorded():
    import json

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "decode_step_trace.json")
    with open(path) as f:
        d = json.load(f)
    ev = lambda xs: [tuple(e) for e in xs]
    return tr.Trace(ops={p: ev(x) for p, x in d["ops"].items()},
                    modules={p: ev(x) for p, x in d["modules"].items()},
                    host=ev(d["host"]))


def test_recorded_decode_step_finds_its_program_and_draw_kernels():
    t = recorded()
    lo, hi = tr.window(t, "bench.window")
    step, n = tr.module_time(t, ("jit_step",), lo, hi)
    assert n == 1 and step == pytest.approx(0.055167038)
    kt, kn = tr.op_time(t, ("%butterfly_sample_truncated_pallas",), lo, hi)
    assert kn >= 2 and 0 < kt < step
    busy, win = tr.busy_share(t, lo, hi)
    assert step * 0.9 < busy <= win


def test_metric_readers_on_the_recorded_step():
    from bench import harness, work

    cfg = harness.load_json(harness.BENCH, "configs", "qwen3-4b.json")
    t = recorded()
    rec = {"trace": t, "window_ns": tr.window(t, "bench.window"), "config": cfg,
           "engine": {"max_slots": 16}, "peaks": work.peaks("TPU v5 lite"),
           "trace_t": (0.0, 1.0), "steps": [(0.5, 16, 16 * 600)]}
    draw = harness.load_module("metrics", "decode_draw_roofline").read(rec)
    mfu = harness.load_module("metrics", "decode.mfu").read(rec)
    idle = harness.metric_reader("idle_share.batch").read(rec)
    assert 0 < draw <= 100 and 0 < mfu <= 100 and 0 <= idle < 100
