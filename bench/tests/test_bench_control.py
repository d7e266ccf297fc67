"""The control of each configuration, at a size a test run holds: the
reference in the precision below the configuration's, put in the program's
place, has to read ``correct`` false.  The same control runs on the chip at
the cells' own sizes through ``bench/control.py``."""

import pytest

import bench_small as small

from bench import harness


@pytest.mark.parametrize("cell", small.LDA_CELLS)
def test_lda_control_in_bfloat16_is_not_correct(cell):
    patch = harness.load_module("controls", "lda").patch
    out = small.run(cell, small.lda_files(cell), patch=patch)
    assert not out["correct"], out["checks"]


def test_serving_control_is_not_correct():
    cell = "qwen3-4b.batch"
    patch = harness.load_module("controls", "qwen3").patch
    sound = small.run(cell, small.serve_files(cell))
    out = small.run(cell, small.serve_files(cell), patch=patch)
    for k in ("greedy_gap", "topk_gap"):
        print(k, sound["checks"][k]["value"], out["checks"][k]["value"])
    assert not out["correct"], out["checks"]
