"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Every other test runs the kernels through the Pallas interpreter, which
accepts blocks and VMEM footprints the chip's compiler refuses.  These
tests compile each kernel with ``interpret=False`` against a described
(not attached) v5e chip, so a kernel change the chip would reject fails
here, with no chip.  Shapes: the decode draw at qwen3's vocabulary
(8 x 151936), the LDA z-draw at the paper's corpus (K=240, V=37286, one
256-document chunk of 107-token rows), the alias build at K=4096, and the
fused draw at the edge of its VMEM budget for each block width.  One more
compiles a whole model's serving programs, as the benchmark's MLA + MoE
cell runs them: Moonlight-16B-A3B's EP-8 share in bf16, the decode step
over 64 slots of a 1024-position latent cache and the largest prefill
bucket, each within one chip's memory.  Two more compile the serving
decode steps of qwen3-4b and Moonlight with the cache donated, and check
that the step writes its new rows into that cache in place.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.alias_build import ops as alias_ops
from repro.kernels.butterfly_sample import kernel as bk
from repro.kernels.lda_draw import kernel as lk

VOCAB = 151936                      # qwen3-4b
K, V = 240, 37286                   # configs/lda.py
B_LDA = 256 * 107                   # one chunk of 256 documents
f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Programs compiled for a described chip cannot be read back from
    the persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _edge_kp(W: int) -> int:
    """The widest lane-aligned row the one-kernel fused route accepts."""
    return max(
        k for k in range(128, 1 << 17, 128)
        if k % W == 0 and bk._fused_fits(8, k, W)
    )


# name -> (function, argument shapes, tpu_custom_call ops expected)
CASES = {
    "draw_decode_two_pass": (
        functools.partial(bk.butterfly_sample_pallas, W=128, tb=8,
                          interpret=False),
        [((8, VOCAB), f32), ((8,), f32)], 2,
    ),
    "draw_lda_fused": (
        functools.partial(bk.butterfly_sample_pallas, W=32, tb=8,
                          interpret=False),
        [((B_LDA, K), f32), ((B_LDA,), f32)], 1,
    ),
    "rng_draw_decode": (
        functools.partial(bk.butterfly_sample_rng_pallas, W=128, tb=8,
                          interpret=False),
        [((8, VOCAB), f32), ((2,), u32)], 2,
    ),
    "truncated_decode_masked_two_pass": (
        functools.partial(bk.butterfly_sample_truncated_pallas, W=128, tb=8,
                          interpret=False),
        [((8, VOCAB), f32), ((8,), f32), ((8, 3), f32)], 2,
    ),
    "truncated_fused": (
        functools.partial(bk.butterfly_sample_truncated_pallas, W=32, tb=8,
                          interpret=False),
        [((8, 4096), f32), ((8,), f32), ((8, 3), f32)], 1,
    ),
    "lda_fused": (
        functools.partial(lk.lda_fused_draw_pallas, W=16, tb=16,
                          interpret=False),
        [((256, K), f32), ((V, K), f32), ((B_LDA,), i32), ((B_LDA,), i32),
         ((B_LDA,), f32)], 1,
    ),
    "lda_pass_a": (
        functools.partial(lk.lda_blocksums_pallas, W=16, tb=16,
                          interpret=False),
        [((256, K), f32), ((V, K), f32), ((B_LDA,), i32), ((B_LDA,), i32)], 1,
    ),
    "lda_pass_b": (
        functools.partial(lk.lda_walk_pallas, W=16, tb=16, interpret=False),
        [((256, K), f32), ((V, K), f32), ((B_LDA, K // 16), f32),
         ((B_LDA,), f32)] + [((B_LDA,), i32)] * 4, 1,
    ),
    "alias_build": (
        functools.partial(alias_ops.build_alias_tables_device, impl="pallas",
                          interpret=False),
        [((8, 4096), f32)], 1,
    ),
}
CASES.update({
    f"fused_edge_W{W}": (
        functools.partial(bk.fused_draw_pallas, W=W, tb=16, interpret=False),
        [((64, _edge_kp(W)), f32), ((64,), f32)], 1,
    )
    for W in (8, 32, 128)
})


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes, n_kernels = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == n_kernels


def test_moonlight_share_serving_programs_fit_one_v5e(one_chip, no_compile_cache):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.params import is_spec

    model = build_model(get_config("moonlight-16b-a3b-ep8"))

    def shapes(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one_chip),
            tree, is_leaf=is_spec)

    params, caches = shapes(model.specs), shapes(model.cache_specs(64, 1024))
    tok = jax.ShapeDtypeStruct((64, 1), i32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((64,), i32, sharding=one_chip)
    prompt = jax.ShapeDtypeStruct((1, 256), i32, sharding=one_chip)
    decode = jax.jit(model.decode).lower(params, caches, tok, pos).compile()
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t})[1]).lower(
        params, prompt).compile()
    for c in (decode, prefill):
        m = c.memory_analysis()
        used = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
        assert used < 15 * 2**30, m


@pytest.mark.parametrize("arch,slots", [("qwen3-4b", 16), ("moonlight-16b-a3b-ep8", 64)])
def test_decode_step_writes_its_rows_in_place_on_v5e(arch, slots, one_chip,
                                                     no_compile_cache):
    """The benchmark's serving decode steps, as the chip's compiler lays
    them out: with the cache donated, the output aliases the whole cache
    and the step's temporaries stay a small fraction of it, so no layer
    rewrite or relayout of the stacked cache came back."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.params import is_spec

    model = build_model(get_config(arch))

    def shapes(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one_chip),
            tree, is_leaf=is_spec)

    caches = shapes(model.cache_specs(slots, 1024))
    cache_bytes = sum(x.size * 2 for x in jax.tree.leaves(caches))
    tok = jax.ShapeDtypeStruct((slots, 1), i32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), i32, sharding=one_chip)
    m = jax.jit(model.decode, donate_argnums=1).lower(
        shapes(model.specs), caches, tok, pos).compile().memory_analysis()
    assert m.alias_size_in_bytes >= cache_bytes, m
    assert m.temp_size_in_bytes < cache_bytes / 20, m
