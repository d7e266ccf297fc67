"""Required-work counts and peaks, pinned to shapes."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import harness, work  # noqa: E402

QWEN = harness.load_json(harness.BENCH, "configs", "qwen3-4b.json")
LDA = harness.load_json(harness.BENCH, "configs", "lda-paper.json")


def test_qwen3_4b_parameter_count():
    assert work.qwen3_params(QWEN) == 4_022_468_096


def test_qwen3_4b_kv_bytes_per_token_is_144_kib():
    assert work.qwen3_kv_bytes_per_token(QWEN) == 144 * 1024


def test_qwen3_4b_matmul_params_exclude_norms_and_embedding():
    per_layer = 2560 * 4096 * 2 + 2 * 2560 * 1024 + 3 * 2560 * 9728
    assert work.qwen3_matmul_params(QWEN) == 36 * per_layer


def test_prefill_flops_count_causal_attention_once():
    s = 256
    attn = 36 * 2 * 2 * (s * (s + 1) // 2) * 32 * 128
    assert work.qwen3_prefill_flops(QWEN, s) == 2 * work.qwen3_matmul_params(QWEN) * s + attn


def test_decode_step_reads_every_parameter_once_plus_attended_kv():
    flops, nbytes = work.qwen3_decode_work(QWEN, active=16, attended=16 * 500)
    assert nbytes == 4_022_468_096 * 2 + (16 * 500 + 16) * 147456
    assert flops > 2 * 16 * work.qwen3_matmul_params(QWEN)


def test_lda_draw_work_at_the_papers_shape():
    tokens = 3_070_000
    flops, nbytes = work.lda_draw_work(tokens, LDA["M"], LDA["V"], LDA["K"])
    assert flops == 2 * 240 * tokens
    assert nbytes == tokens * 240 * 4 + 43556 * 240 * 4 + tokens * 8


def test_lda_sweep_adds_the_dirichlet_writes_and_count_reads():
    d = work.lda_draw_work(1000, 10, 20, 8)[1]
    s = work.lda_sweep_work(1000, 10, 20, 8)[1]
    assert s - d == (10 * 8 + 20 * 8) * 4 + 1000 * 4


def test_vocab_draw_bytes():
    assert work.vocab_draw_bytes(16, 151936) == 16 * 151936 * 4 + 16 * 4


def test_v5e_peaks_and_unknown_device():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16 * 2**30
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_least_time_takes_the_binding_bound():
    pk = work.peaks("TPU v5 lite")
    assert work.least_time_s(197e12, 0, pk) == pytest.approx(1.0)
    assert work.least_time_s(1.0, 819e9, pk) == pytest.approx(1.0)
