"""A run of the LDA cell with its timed path broken underneath reads
``correct`` false, once for each fault the cell can have.  (One chip: no
exchange between chips to leave out.)"""

import functools

import pytest

import bench_small as small

from repro.lda import gibbs


def unchanged(orig):
    return lambda state, corpus, **kw: state


def half_left_out(orig):
    def step(state, corpus, **kw):
        new = orig(state, corpus, **kw)
        h = state.z.shape[0] // 2
        return new._replace(z=new.z.at[h:].set(state.z[h:]),
                            theta=new.theta.at[h:].set(state.theta[h:]))
    return step


def topic_altered(orig):
    def step(state, corpus, **kw):
        new = orig(state, corpus, **kw)
        K = new.theta.shape[1]
        return new._replace(z=(new.z + 1) % K)
    return step


@pytest.mark.parametrize("cell", small.LDA_CELLS)
def test_sound_run_is_correct(cell):
    out = small.run(cell, small.lda_files(cell))
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", small.LDA_CELLS)
@pytest.mark.parametrize("fault", [unchanged, half_left_out, topic_altered],
                         ids=lambda f: f.__name__)
def test_fault_reads_not_correct(fault, cell):
    patch = functools.partial(small.replaced, gibbs, "gibbs_step", fault)
    out = small.run(cell, small.lda_files(cell), patch=patch)
    assert not out["correct"], out["checks"]
