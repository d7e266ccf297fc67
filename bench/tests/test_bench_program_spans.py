"""The readers of the program's own spans (bench/program_spans.py and the
program_span / program_counter metrics), on hand-made span rings."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, program_spans  # noqa: E402
from repro.obs import Span  # noqa: E402

MS = 1_000_000
SERVE = ("queue_wait_p85_ms.chat", "admit_ms.chat", "step_host_ms.chat",
         "step_host_ms.batch", "recompiles.chat", "recompiles.batch")
LDA = ("lda_upload_ms", "recompiles.lda")
NEW = SERVE + LDA


class Ring:
    """Spans made by hand, times in ms, ids in the order they open."""

    def __init__(self):
        self.spans = []

    def add(self, name, start_ms, end_ms, parent=None, **attrs):
        s = Span(len(self.spans) + 1, parent, name, int(start_ms * MS),
                 int(end_ms * MS), attrs)
        self.spans.append(s)
        return s.id

    def step(self, t, dispatch, walk, compile_ms=0.0):
        st = self.add("engine.step", t, t + 60)
        d = self.add("engine.step.dispatch", t, t + dispatch, st)
        if compile_ms:
            self.add("jax.compile", t, t + compile_ms, d, fun_name="jit(step)")
        self.add("engine.step.wait", t + dispatch, t + 58 - walk, st)
        self.add("engine.step.walk", t + 58 - walk, t + 58, st)


def serving_ring():
    """Warm-up at 1-2 s, the window from 10 s: three requests, three steps,
    one compile in the window and one in warm-up."""
    r = Ring()
    r.add("engine.queue", 900, 1000, req=0)
    r.add("engine.admit", 1000, 1500, req=0)
    r.step(1600, dispatch=9.0, walk=9.0, compile_ms=300)
    for i, (arrive, queued, admit) in enumerate([(10_000, 1, 20), (10_100, 3, 30),
                                                 (10_200, 10, 40)]):
        r.add("engine.queue", arrive, arrive + queued, req=i + 1)
        r.add("engine.admit", arrive + queued, arrive + queued + admit, req=i + 1)
    r.step(10_300, dispatch=2.0, walk=1.0, compile_ms=100)
    r.step(10_400, dispatch=3.0, walk=1.5)
    r.step(10_500, dispatch=4.0, walk=2.5)
    return r.spans


def lda_ring():
    """Two checked sweeps in set-up, then three in the window, each with
    its upload; a compile in a set-up sweep and one in a window sweep."""
    r = Ring()
    for i, upload in enumerate([50.0, 40.0, 17.0, 19.0, 18.0]):
        t = 1000.0 * i
        sw = r.add("lda.sweep", t, t + 900, index=i)
        r.add("lda.upload", t, t + upload, sw)
        d = r.add("lda.dispatch", t + upload, t + upload + 5, sw)
        if i in (1, 3):
            r.add("jax.compile", t + upload, t + upload + 2, d, fun_name="jit(impl)")
    r.add("lda.draw_z", 9000, 9500)
    return r.spans


SERVE_REC = {"window": (10.0, 61.0)}
LDA_REC = {"sweeps": 3}
EXPECTED = {
    "queue_wait_p85_ms.chat": float(np.percentile([1.0, 3.0, 10.0], 85)),
    "admit_ms.chat": 30.0,
    "step_host_ms.chat": 4.5,     # 2+1, 3+1.5, 4+2.5
    "step_host_ms.batch": 4.5,
    "recompiles.chat": 1.0,
    "recompiles.batch": 1.0,
    "lda_upload_ms": 18.0,        # the last three sweeps: 17, 19, 18
    "recompiles.lda": 1.0,
}


def read(name, rec, spans, monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: spans)
    return harness.metric_reader(name).read(rec)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_the_window(name, monkeypatch):
    rec, spans = (SERVE_REC, serving_ring()) if name in SERVE else (LDA_REC, lda_ring())
    assert read(name, rec, spans, monkeypatch) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_when_no_span_is_selected(name, monkeypatch):
    if name in SERVE:
        rec, spans = {"window": (100.0, 151.0)}, serving_ring()
    else:
        rec, spans = {"sweeps": 0}, lda_ring()
    assert read(name, rec, spans, monkeypatch) is None
    assert read(name, rec, [], monkeypatch) is None
    # a program with no span record at all
    assert read(name, SERVE_REC if name in SERVE else LDA_REC, None, monkeypatch) is None


def test_lda_selection_takes_only_the_last_sweeps(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lda_ring)
    got = program_spans.select({"sweeps": 3})
    sweeps = [s for s in got if s.name == "lda.sweep"]
    assert [s.attrs["index"] for s in sweeps] == [2, 3, 4]
    assert all(s.name != "lda.draw_z" for s in got)
    # more sweeps asked for than recorded: all of them
    got = program_spans.select({"sweeps": 9})
    assert len([s for s in got if s.name == "lda.sweep"]) == 5


def test_serving_selection_starts_at_the_window(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", serving_ring)
    got = program_spans.select(SERVE_REC)
    assert min(s.start_ns for s in got) == 10_000 * MS
    assert sorted(s.attrs["req"] for s in got if s.name == "engine.admit") == [1, 2, 3]
    assert [s.attrs["fun_name"] for s in got if s.name == "jax.compile"] == ["jit(step)"]


def test_the_new_metrics_are_in_the_manifest():
    per_layer = {m["name"]: m for m in harness.manifest()["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["better"] == "lower"
        want = "program_counter" if name.startswith("recompiles") else "program_span"
        assert m["source"] == want
        assert m["unit"] == ("count" if name.startswith("recompiles") else "ms")
