"""The decode step's cache write: new K/V (or latent) rows only, in place.

Without an activation mesh a decode step attends the stored cache as it
is, plus each row's own new token, and writes only the new rows into the
stacked cache after the layer scan; the engine donates the cache, so the
write reuses its buffers.  Under a mesh each layer still rewrites its
cache with a one-hot ``where``, which keeps a sequence-sharded cache in
its shards.  Pinned here:

* the engine's step and insert alias their cache outputs onto the donated
  inputs (dense and MLA with a ``lead`` stack);
* one layer's decode attention, and the cache it leaves, equal the
  former form's (one-hot write, then attention over the whole cache) on
  both write paths, for GQA with and without a window and with softcap,
  MLA, per-row and scalar positions;
* a whole decode step gives the same logits and caches on both write
  paths, for every architecture.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ModelConfig, SamplerSpec
from repro.dist import sharding as shd
from repro.models import attention
from repro.models.model import build_model
from repro.models.params import init_params, is_spec
from repro.serve import ContinuousBatchingEngine, Request

B, S = 5, 12
POS = np.array([0, S - 1, 3, 3, 7], np.int32)   # first, last, shared


@pytest.fixture
def act_mesh():
    """A one-device activation mesh, so the layers take the mesh's write
    path; always reset after."""
    def set_(on):
        shd.set_activation_sharding(
            Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
            if on else None)
    yield set_
    shd.set_activation_sharding(None)


def _random_tree(specs, seed):
    leaves, tdef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tdef, [
        jax.random.normal(k, s.shape, jnp.float32) for k, s in zip(keys, leaves)])


# -- donation ----------------------------------------------------------------

DENSE = ModelConfig(
    name="tiny-inplace", family="dense", num_layers=4, d_model=32,
    num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
    sampler=SamplerSpec(method="fenwick", W=8),
)


@pytest.mark.parametrize("arch", ["dense", "moonlight-16b-a3b-ep8"])
def test_step_and_insert_write_into_the_donated_cache(arch):
    """The compiled step and insert alias their cache outputs onto the
    cache they are given (without donation the alias reads 0), and a
    step leaves the buffers it was given deleted."""
    cfg = DENSE if arch == "dense" else dataclasses.replace(
        get_config(arch, smoke=True), sampler=SamplerSpec(method="fenwick", W=8))
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)
    eng = ContinuousBatchingEngine(model, params, max_slots=4, max_len=256)
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(eng._caches))
    Bs = eng.max_slots
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    step_args = (params, eng._caches, jnp.zeros((Bs,), i32), jnp.zeros((Bs,), i32),
                 jnp.zeros((Bs, 2), u32), jnp.zeros((Bs,), u32),
                 jnp.ones((Bs,), f32), jnp.zeros((Bs, 3), f32))
    step = eng._step.lower(*step_args).compile().memory_analysis()
    insert = eng._insert.lower(eng._caches, eng._empty_prefix, i32(0)).compile()
    for m in (step, insert.memory_analysis()):
        assert m.alias_size_in_bytes >= cache_bytes, m

    given = eng._caches
    eng.run([Request(prompt=np.arange(1, 4, dtype=np.int32), max_new_tokens=2)])
    assert all(x.is_deleted() for x in jax.tree.leaves(given))
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng._caches))


# -- one layer against the former form ---------------------------------------

GQA = ModelConfig(
    name="tiny-gqa", family="dense", num_layers=1, d_model=32, num_heads=4,
    num_kv_heads=2, head_dim=8, d_ff=32, vocab_size=64, qk_norm=True,
)


def _one_hot_write(cache, new, pos):
    """The former write: row b of ``new`` (B, 1, ...) at ``pos[b]``."""
    oh = jnp.arange(cache.shape[1])[None, :] == pos[:, None]
    return jnp.where(oh.reshape(oh.shape + (1,) * (cache.ndim - 2)), new, cache)


def _former_gqa(p, x, positions, cfg, window, cache, pos):
    """One-hot write, then attention over the whole cache, positions up
    to and including each row's own."""
    q, k, v = attention.gqa_project_qkv(p, x, positions, cfg)
    ck, cv = _one_hot_write(cache["k"], k, pos), _one_hot_write(cache["v"], v, pos)
    k_pos = jnp.arange(ck.shape[1])[None, :]
    qp = pos[:, None]
    mask = (k_pos <= qp) & ((k_pos > qp - window) if window > 0 else True)
    scores = jnp.where(mask[:, None, None, None],
                       attention._group_scores(q, ck, cfg.attn_softcap),
                       attention.NEG_INF)
    out = attention._group_context(jax.nn.softmax(scores, -1), cv)
    out = out.reshape(q.shape[:3] + (cv.shape[-1],))
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"]), {"k": ck, "v": cv}


def _former_mla(p, x, positions, cfg, window, cache, pos):
    """The former absorbed decode: one-hot latent write, then scores and
    context over the whole latent cache."""
    m = cfg.mla
    c_new, kpe_new = attention._mla_latents(p, x, positions, cfg)
    c_kv = _one_hot_write(cache["c_kv"], c_new, pos)
    k_pe = _one_hot_write(cache["k_pe"], kpe_new, pos)
    q_nope, q_pe = attention._mla_queries(p, x, positions, cfg)
    q_c = jnp.einsum("bsnh,rnh->bsnr", q_nope, p["wuk"])
    scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_head_dim + m.qk_rope_head_dim))
    scores = (jnp.einsum("bsnr,btr->bnst", q_c, c_kv)
              + jnp.einsum("bsnh,bth->bnst", q_pe, k_pe)) * scale
    valid = jnp.arange(c_kv.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, attention.NEG_INF)
    ctx = jnp.einsum("bnst,btr->bsnr", jax.nn.softmax(scores, -1), c_kv)
    out = jnp.einsum("bsnr,rnh->bsnh", ctx, p["wuv"])
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"]), {"c_kv": c_kv, "k_pe": k_pe}


# name -> (config, window, positions per row (else one scalar position))
LAYER_CASES = {
    "gqa": (GQA, 0, True),
    "gqa_window": (GQA, 4, True),
    "gqa_softcap": (dataclasses.replace(GQA, attn_softcap=0.5), 0, True),
    "gqa_window_scalar_pos": (GQA, 4, False),
    "mla": (get_config("moonlight-16b-a3b", smoke=True), 0, True),
    "mla_scalar_pos": (get_config("moonlight-16b-a3b", smoke=True), 0, False),
}


@pytest.mark.parametrize("on_mesh", [False, True], ids=["rows", "one_hot"])
@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_decode_layer_equals_the_one_hot_form(case, on_mesh, act_mesh):
    cfg, window, per_row = LAYER_CASES[case]
    mla = cfg.attention == "mla"
    spec = attention.mla_spec(cfg) if mla else attention.gqa_spec(cfg)
    cspec = (attention.mla_cache_spec if mla else attention.gqa_cache_spec)(cfg, B, S)
    p, cache = _random_tree(spec, 0), _random_tree(cspec, 1)
    p = jax.tree.map(lambda a: a * 0.3, p)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 1, cfg.d_model), jnp.float32)
    pos = jnp.asarray(POS) if per_row else jnp.int32(6)
    positions = pos[:, None] if per_row else pos[None]
    pos_b = jnp.broadcast_to(pos, (B,))

    want_y, want_cache = (_former_mla if mla else _former_gqa)(
        p, x, positions, cfg, window, cache, pos_b)
    act_mesh(on_mesh)
    if mla:
        y, out = attention.mla_attend_decode(p, x, cache, pos, cfg)
    else:
        y, out = attention.gqa_attend(p, x, positions, cfg, causal=False,
                                      window=window, cache=cache, cache_pos=pos)
    if not on_mesh:   # the new rows, written as the stack's write does
        assert all(a.shape[1] == 1 for a in out.values())
        out = {k: attention.write_rows(cache[k][None], r[None], pos)[0]
               for k, r in out.items()}
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5, atol=1e-6)
    for k in want_cache:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(want_cache[k]))


# -- a whole decode step, both write paths -----------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_rows_equal_one_hot_write(arch, act_mesh):
    """Per-row positions (first, last, shared) through every layer kind:
    logits and every cache leaf agree between the in-place rows and the
    mesh's one-hot rewrite."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)
    # an encoder-decoder splits max_len between its self and cross caches
    caches = _random_tree(model.cache_specs(B, 2 * S if cfg.encoder_layers else S), 1)
    toks = jnp.asarray(np.arange(B, dtype=np.int32)[:, None] + 1)
    pos = jnp.asarray(POS)

    def run():   # a fresh function: the mesh is read when it is traced
        return jax.jit(lambda *a: model.decode(*a))(params, caches, toks, pos)

    act_mesh(False)
    rows = run()
    act_mesh(True)
    one_hot = run()
    np.testing.assert_allclose(np.asarray(rows[0]), np.asarray(one_hot[0]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(rows[1]), jax.tree.leaves(one_hot[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
