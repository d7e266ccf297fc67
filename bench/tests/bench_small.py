"""Small-size runs of the benchmark's cells on the CPU, for the tests: the
harness's own set-up, window and check, with the look for a chip skipped."""

import contextlib
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

LDA_CELLS = ("lda-paper.sweep", "lda-paper.sparse")
SERVE_CELLS = ("qwen3-4b.chat", "qwen3-4b.batch")


def lda_files(cell):
    wl, cfg, tr = harness.cell_files(cell)
    return dict(wl, check_sweeps=2), dict(cfg, M=300, V=400, K=16), tr


def serve_files(cell):
    wl, cfg, tr = harness.cell_files(cell)
    cfg = dict(cfg, num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=128,
               vocab_size=512, initializer_range=0.25)
    wl = dict(wl, engine=dict(wl["engine"], max_len=96, max_slots=4),
              check_requests=4, backlog=24, rate_per_s=6.0, drain_s=60)
    tr = dict(tr, prompt_len=dict(tr["prompt_len"], median=16, min=4, max=48),
              output_len=dict(tr["output_len"], median=10, min=4, max=40))
    return wl, cfg, tr


def run(cell, files, seed=2**33 + 5, seconds=1.5, patch=contextlib.nullcontext):
    """The result line of one small run on the CPU."""
    with patch():
        return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                                files=files, require_chip=False)


@contextlib.contextmanager
def replaced(obj, name, fn):
    orig = getattr(obj, name)
    setattr(obj, name, fn(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)
