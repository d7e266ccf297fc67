"""A run of a serving cell with its timed path broken underneath reads
``correct`` false, once for each fault the cell can have.  (One chip: no
exchange between chips to leave out.)"""

import functools

import jax.numpy as jnp
import pytest

import bench_small as small

from repro.models import transformer


def cache_unchanged(orig):
    def decode(cfg, params, caches, tokens, cache_pos):
        logits, _ = orig(cfg, params, caches, tokens, cache_pos)
        return logits, caches
    return decode


def half_batch_left_out(orig):
    def decode(cfg, params, caches, tokens, cache_pos):
        logits, new = orig(cfg, params, caches, tokens, cache_pos)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), new
    return decode


def token_altered(orig):
    def decode(cfg, params, caches, tokens, cache_pos):
        logits, new = orig(cfg, params, caches, tokens, cache_pos)
        return jnp.roll(logits, 1, axis=-1), new
    return decode


@pytest.mark.parametrize("cell", small.SERVE_CELLS)
def test_sound_run_is_correct(cell):
    out = small.run(cell, small.serve_files(cell))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", [cache_unchanged, half_batch_left_out, token_altered],
                         ids=lambda f: f.__name__)
def test_fault_reads_not_correct(fault):
    # the backlog keeps every slot busy, so every slot's tokens are checked
    cell = "qwen3-4b.batch"
    patch = functools.partial(small.replaced, transformer, "lm_decode", fault)
    out = small.run(cell, small.serve_files(cell), patch=patch)
    assert not out["correct"], out["checks"]
