"""Continuous-batching engine (repro.serve.batching): lifecycle, admission
control, slot-recycling bit-identity, and the zero-retrace gate.

Bit-identity tests pin a fixed sampler method.  Serving routes MoE layers
drop-free (every token through each held expert, no capacity), so a row's
output depends on that row alone and "batched == one at a time" holds for
MoE + MLA models as for dense ones; both are pinned.
"""

import asyncio

import numpy as np
import pytest

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ModelConfig, SamplerSpec
from repro.models.model import build_model
from repro.models.params import init_params
from repro.serve import (
    ContinuousBatchingEngine,
    QueueFullError,
    Request,
    RequestState,
    FinishReason,
    SamplingParams,
)

CFG = ModelConfig(
    name="tiny-serve", family="dense", num_layers=2, d_model=32,
    num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
    sampler=SamplerSpec(method="fenwick", W=8),
)


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)
    return model, params


def _req(i, plen=3, max_new=4, **sp):
    return Request(
        prompt=np.arange(1, 1 + plen, dtype=np.int32),
        max_new_tokens=max_new,
        seed=100 + i,
        sampling=SamplingParams(**sp) if sp else SamplingParams(),
    )


# -- lifecycle ---------------------------------------------------------------


def test_lifecycle_three_requests_two_slots(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32)
    out = eng.run([_req(i, plen=2 + i, max_new=3 + i) for i in range(3)])
    for i, r in enumerate(out):
        assert r.state is RequestState.FINISHED
        assert r.finish_reason is FinishReason.LENGTH
        assert len(r.output_tokens) == 3 + i
        assert all(0 <= t < CFG.vocab_size for t in r.output_tokens)
    st = eng.stats()
    assert st["submitted"] == 3 and st["finished"] == 3
    assert eng.scheduler.idle


def test_recycling_bit_identity_vs_sequential(model_and_params):
    """3 requests churning through 2 slots produce bit-identical tokens
    to one-at-a-time runs with the same per-request seeds — the
    counter-RNG slot-isolation invariant."""
    _assert_batched_equals_sequential(*model_and_params)


def test_recycling_bit_identity_moe_mla():
    """The same for an MoE + MLA model (Moonlight's block at smoke size,
    one chip's share of its experts, a leading dense layer): drop-free
    routing keeps batchmates out of a row's experts."""
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b-ep8", smoke=True),
                              sampler=SamplerSpec(method="fenwick", W=8))
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs, jnp.float32)
    _assert_batched_equals_sequential(model, params)


def _assert_batched_equals_sequential(model, params):
    def reqs():
        return [
            _req(i, plen=2 + i, max_new=4 + i, temperature=0.8, top_p=0.95)
            for i in range(3)
        ]

    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32)
    batched = [r.output_tokens for r in eng.run(reqs())]
    sequential = []
    for r in reqs():
        one = ContinuousBatchingEngine(model, params, max_slots=1, max_len=32)
        sequential.append(one.run([r])[0].output_tokens)
    assert batched == sequential


@pytest.mark.parametrize("method", ["fenwick", "butterfly"])
def test_recycling_bit_identity_methods(method, model_and_params):
    model, params = model_and_params
    cfg = ModelConfig(**{
        **{f.name: getattr(CFG, f.name) for f in CFG.__dataclass_fields__.values()},
        "sampler": SamplerSpec(method=method, W=8),
    })
    m = build_model(cfg)
    eng = ContinuousBatchingEngine(m, params, max_slots=2, max_len=32)
    batched = [
        r.output_tokens
        for r in eng.run([_req(i, max_new=5, temperature=0.9) for i in range(3)])
    ]
    one = ContinuousBatchingEngine(m, params, max_slots=1, max_len=32)
    solo = one.run([_req(1, max_new=5, temperature=0.9)])[0].output_tokens
    assert batched[1] == solo


def test_single_token_prompt(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=16)
    r = eng.run([Request(prompt=np.array([5]), max_new_tokens=3, seed=7)])[0]
    assert r.finish_reason is FinishReason.LENGTH
    assert len(r.output_tokens) == 3


def test_eos_early_finish(model_and_params):
    model, params = model_and_params
    probe = ContinuousBatchingEngine(model, params, max_slots=1, max_len=32)
    first = probe.run([_req(0, max_new=1, temperature=0.8)])[0].output_tokens[0]
    eng = ContinuousBatchingEngine(
        model, params, max_slots=2, max_len=32, eos_id=first
    )
    r = eng.run([_req(0, max_new=8, temperature=0.8)])[0]
    assert r.finish_reason is FinishReason.EOS
    assert r.output_tokens == [first]


def test_greedy_temperature_zero_matches_argmax(model_and_params):
    """A temperature=0 request in a heterogeneous batch decodes greedily
    while its batchmates sample."""
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32)
    out = eng.run([
        _req(0, max_new=4, temperature=0.0),
        _req(1, max_new=4, temperature=1.2, top_k=10),
    ])
    solo = ContinuousBatchingEngine(model, params, max_slots=1, max_len=32)
    greedy = solo.run([_req(0, max_new=4, temperature=0.0)])[0].output_tokens
    assert out[0].output_tokens == greedy


def test_top_k_one_is_argmax_in_heterogeneous_batch(model_and_params):
    """top_k=1 must collapse a sampling row to argmax even while the rest
    of the batch draws with different params — the per-row truncation
    thresholds actually apply per row."""
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=3, max_len=32)
    out = eng.run([
        _req(0, max_new=5, temperature=1.0, top_k=1),
        _req(1, max_new=5, temperature=1.3, top_p=0.8),
        _req(2, max_new=5, temperature=0.0),
    ])
    solo = ContinuousBatchingEngine(model, params, max_slots=1, max_len=32)
    greedy = solo.run([_req(0, max_new=5, temperature=0.0)])[0].output_tokens
    assert out[0].output_tokens == greedy


# -- admission control -------------------------------------------------------


def test_admission_rejects_beyond_max_waiting(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(
        model, params, max_slots=1, max_len=32, max_waiting=2
    )
    eng.submit_nowait(_req(0))
    eng.submit_nowait(_req(1))
    with pytest.raises(QueueFullError):
        eng.submit_nowait(_req(2))
    assert eng.stats()["rejected"] == 1
    # the admitted two still complete
    out = eng.run([])
    assert eng.stats()["finished"] == 2
    assert out == []


def test_rejects_over_budget_request(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=1, max_len=8)
    bad = Request(prompt=np.arange(5), max_new_tokens=10, seed=0)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit_nowait(bad)
    assert bad.state is RequestState.REJECTED


def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request(prompt=np.array([], np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(prompt=np.array([1]), max_new_tokens=0)
    with pytest.raises(ValueError, match="concrete scalar"):
        Request(prompt=np.array([1]), sampling=SamplingParams(top_p=np.ones(4)))


def test_rejects_non_decoder_configs():
    cfg = ModelConfig(
        name="encdec", family="encdec", num_layers=2, d_model=32,
        num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
        encoder_layers=2,
    )
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)
    with pytest.raises(ValueError, match="decoder-only"):
        ContinuousBatchingEngine(model, params, max_slots=2)


# -- the decode draw's plan --------------------------------------------------

def _engine_serves(cfg):
    """The engine refuses encoder, frontend and meta-token configs."""
    return not (cfg.encoder_layers or cfg.frontend_len or cfg.meta_tokens)


ENGINE_ARCHS = [a for a in ARCH_IDS if _engine_serves(get_config(a))]


@pytest.mark.parametrize("arch", ENGINE_ARCHS)
def test_engine_draw_plan_per_config(arch, monkeypatch):
    """On TPU every config the engine serves draws through the fused
    kernel, launched with W=128 and (tb, tk) = (8, 512) at its vocabulary."""
    import types

    cfg = get_config(arch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stub = types.SimpleNamespace(model=types.SimpleNamespace(cfg=cfg), mesh=None)
    plan, local = ContinuousBatchingEngine._resolve_plans(
        stub, cfg.serve_spec.max_slots, cfg.padded_vocab
    )
    assert local is None
    assert (plan.method, plan.W, plan.tb, plan.tk) == ("kernel", 128, 8, 512)


# -- the zero-retrace gate ---------------------------------------------------


@pytest.mark.slow
def test_zero_recompiles_under_churn(model_and_params):
    """>= 20 requests with heterogeneous SamplingParams and varying
    prompt/output lengths churning through 8 slots: the decode step
    compiles exactly once (warmup), and never again."""
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=8, max_len=64)
    eng.warmup(max_prompt_len=16)
    base = eng.compile_stats()
    assert base["decode_step_compiles"] == 1

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(24):
        plen = int(rng.integers(1, 15))
        reqs.append(Request(
            prompt=rng.integers(0, CFG.vocab_size, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(1, 12)),
            seed=i,
            sampling=SamplingParams(
                temperature=[0.0, 0.7, 1.0, 1.3][i % 4],
                top_k=[0, 5, 20, 0][i % 4],
                top_p=[1.0, 0.9, 1.0, 0.8][i % 4],
                min_p=[0.0, 0.0, 0.05, 0.0][i % 4],
            ),
        ))
    out = eng.run(reqs)
    after = eng.compile_stats()
    assert after["decode_step_compiles"] == 1
    assert after["prefill_compiles"] == base["prefill_compiles"]
    assert after["insert_compiles"] == base["insert_compiles"]
    assert all(r.state is RequestState.FINISHED for r in out)
    assert eng.stats()["finished"] >= 24


# -- asyncio surface ---------------------------------------------------------


def test_asyncio_submit_and_drain(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32)

    async def main():
        await eng.start()
        reqs = [await eng.submit(_req(i, max_new=3)) for i in range(4)]
        done = await asyncio.gather(*(r.future for r in reqs))
        await eng.stop()
        return done

    done = asyncio.run(main())
    assert len(done) == 4
    for r in done:
        assert r.state is RequestState.FINISHED
        assert len(r.output_tokens) == 3
        assert r.ttft >= 0 and r.e2e_latency >= r.ttft


def test_asyncio_tokens_match_sync(model_and_params):
    model, params = model_and_params
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32)

    async def main():
        await eng.start()
        reqs = [
            await eng.submit(_req(i, max_new=4, temperature=0.8))
            for i in range(3)
        ]
        await asyncio.gather(*(r.future for r in reqs))
        await eng.stop()
        return [r.output_tokens for r in reqs]

    got = asyncio.run(main())
    sync_eng = ContinuousBatchingEngine(model, params, max_slots=2, max_len=32)
    want = [
        r.output_tokens
        for r in sync_eng.run([_req(i, max_new=4, temperature=0.8) for i in range(3)])
    ]
    assert got == want


# -- sharded decode composition ----------------------------------------------


@pytest.mark.slow
def test_mesh_sharded_engine_bit_identical():
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from jax.sharding import Mesh

    model = build_model(CFG)
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)
    nd = 2 if jax.device_count() % 2 == 0 else jax.device_count()
    mesh = Mesh(
        np.array(jax.devices()).reshape(nd, -1), ("data", "model")
    )

    def reqs():
        return [
            _req(i, plen=2 + i % 3, max_new=4, temperature=0.8, top_p=0.9)
            for i in range(6)
        ]

    sharded = ContinuousBatchingEngine(
        model, params, max_slots=8, max_len=32, mesh=mesh
    )
    got = [r.output_tokens for r in sharded.run(reqs())]
    plain = ContinuousBatchingEngine(model, params, max_slots=8, max_len=32)
    want = [r.output_tokens for r in plain.run(reqs())]
    assert got == want


def test_mesh_requires_divisible_slots():
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import Mesh

    model = build_model(CFG)
    params = init_params(jax.random.PRNGKey(0), model.specs, jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("data", "model"))
    with pytest.raises(ValueError, match="divide"):
        ContinuousBatchingEngine(model, params, max_slots=3, mesh=mesh)
