"""Model-stack correctness: algebraic equivalences between independent
implementations of the same math."""

import dataclasses
import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest


from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, SSMConfig
from repro.models import build_model, init_params
from repro.models.params import init_params as init_cache


V = 64


def _toks(B, S, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.array(rng.integers(0, V, size=(B, S)), jnp.int32)


class TestSSD:
    def test_chunked_matches_naive_recurrence(self):
        """The SSD chunked dual form must equal the step-by-step recurrence."""
        from repro.models.ssm import ssd_chunked

        rng = np.random.default_rng(0)
        B, S, H, P, N = 2, 32, 3, 4, 5
        xh = jnp.array(rng.normal(size=(B, S, H, P)), jnp.float32)
        bh = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
        ch = jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32)
        dt = jnp.array(rng.uniform(0.1, 0.9, size=(B, S, H)), jnp.float32)
        a_log = jnp.array(rng.normal(size=(H,)) * 0.3, jnp.float32)

        y, h_fin = ssd_chunked(xh, bh, ch, dt, a_log, chunk=8)

        # naive recurrence
        A = -np.exp(np.array(a_log))
        h = np.zeros((B, H, P, N))
        ys = np.zeros((B, S, H, P))
        for t in range(S):
            da = np.exp(np.array(dt[:, t]) * A)          # (B,H)
            xb = np.einsum(
                "bhp,bhn->bhpn",
                np.array(xh[:, t]) * np.array(dt[:, t])[..., None],
                np.array(bh[:, t]),
            )
            h = h * da[..., None, None] + xb
            ys[:, t] = np.einsum("bhn,bhpn->bhp", np.array(ch[:, t]), h)
        np.testing.assert_allclose(np.array(y), ys, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.array(h_fin), h, rtol=2e-4, atol=2e-4)

    def test_chunk_size_invariance(self):
        from repro.models.ssm import ssd_chunked

        rng = np.random.default_rng(1)
        B, S, H, P, N = 1, 24, 2, 3, 4
        args = [
            jnp.array(rng.normal(size=(B, S, H, P)), jnp.float32),
            jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32),
            jnp.array(rng.normal(size=(B, S, H, N)), jnp.float32),
            jnp.array(rng.uniform(0.1, 0.9, size=(B, S, H)), jnp.float32),
            jnp.array(rng.normal(size=(H,)) * 0.3, jnp.float32),
        ]
        y8, h8 = ssd_chunked(*args, chunk=8)
        y24, h24 = ssd_chunked(*args, chunk=24)
        np.testing.assert_allclose(np.array(y8), np.array(y24), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.array(h8), np.array(h24), rtol=2e-4, atol=2e-4)


class TestMoE:
    def _cfg(self, dispatch):
        return ModelConfig(
            name="t", family="moe", num_layers=1, d_model=16, num_heads=2,
            num_kv_heads=2, d_ff=0, vocab_size=V, moe_dispatch=dispatch,
            moe=MoEConfig(num_experts=4, top_k=2, expert_d_ff=16, capacity_factor=8.0),
        )

    def test_dispatch_modes_agree(self):
        """einsum (GShard) and gather dispatch must be numerically identical
        when capacity is large enough that nothing drops."""
        from repro.models.moe import moe_block, moe_spec

        cfg = self._cfg("einsum")
        params = init_params(jax.random.PRNGKey(0), moe_spec(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        y_e, aux_e = moe_block(params, x, cfg, "einsum")
        y_g, aux_g = moe_block(params, x, cfg, "gather")
        np.testing.assert_allclose(np.array(y_e), np.array(y_g), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(aux_e), float(aux_g), rtol=1e-5)

    def test_capacity_drops_are_consistent(self):
        """With tight capacity both modes drop the SAME tokens (priority =
        flattened (token, choice) order)."""
        from repro.models.moe import moe_block, moe_spec

        cfg = dataclasses.replace(
            self._cfg("einsum"),
            moe=MoEConfig(num_experts=4, top_k=2, expert_d_ff=16, capacity_factor=0.5),
        )
        params = init_params(jax.random.PRNGKey(0), moe_spec(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16))
        y_e, _ = moe_block(params, x, cfg, "einsum")
        y_g, _ = moe_block(params, x, cfg, "gather")
        np.testing.assert_allclose(np.array(y_e), np.array(y_g), rtol=2e-5, atol=2e-5)

    def test_gradients_flow(self):
        from repro.models.moe import moe_block, moe_spec

        cfg = self._cfg("gather")
        params = init_params(jax.random.PRNGKey(0), moe_spec(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))

        def loss(p):
            y, aux = moe_block(p, x, cfg, "gather")
            return jnp.sum(y**2) + 0.01 * aux

        g = jax.grad(loss)(params)
        norms = [float(jnp.linalg.norm(l)) for l in jax.tree.leaves(g)]
        assert all(np.isfinite(norms))
        assert sum(n > 0 for n in norms) >= 3  # experts + router get grads


class TestAttention:
    def test_window_equals_full_when_wide(self):
        from repro.models.attention import gqa_attend, gqa_spec

        cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=16,
                          num_heads=4, num_kv_heads=2, d_ff=32, vocab_size=V)
        params = init_params(jax.random.PRNGKey(0), gqa_spec(cfg), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
        pos = jnp.arange(12)
        y_full, _ = gqa_attend(params, x, pos, cfg, causal=True, window=0)
        y_wide, _ = gqa_attend(params, x, pos, cfg, causal=True, window=100)
        np.testing.assert_allclose(np.array(y_full), np.array(y_wide), rtol=1e-5, atol=1e-6)
        y_narrow, _ = gqa_attend(params, x, pos, cfg, causal=True, window=2)
        assert not np.allclose(np.array(y_full), np.array(y_narrow), atol=1e-4)

    def test_mla_decode_matches_full(self):
        """Absorbed decode == naive full attention at the same position."""
        cfg = ModelConfig(
            name="t", family="dense", num_layers=1, d_model=16, num_heads=2,
            num_kv_heads=2, d_ff=32, vocab_size=V, attention="mla",
            mla=MLAConfig(q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=4,
                          qk_rope_head_dim=4, v_head_dim=4),
        )
        from repro.models.attention import mla_attend_decode, mla_attend_full, mla_spec

        params = init_params(jax.random.PRNGKey(0), mla_spec(cfg), jnp.float32)
        B, S = 2, 8
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, 16))
        pos = jnp.arange(S)
        y_full, cache = mla_attend_full(params, x, pos, cfg)
        # decode the last position against the cache of the first S-1
        cache_trunc = {
            "c_kv": jnp.concatenate([cache["c_kv"][:, : S - 1], jnp.zeros_like(cache["c_kv"][:, :1])], 1),
            "k_pe": jnp.concatenate([cache["k_pe"][:, : S - 1], jnp.zeros_like(cache["k_pe"][:, :1])], 1),
        }
        y_dec, _ = mla_attend_decode(params, x[:, S - 1 :], cache_trunc, jnp.int32(S - 1), cfg)
        np.testing.assert_allclose(
            np.array(y_dec[:, 0]), np.array(y_full[:, -1]), rtol=2e-4, atol=2e-4
        )


class TestDecodeConsistency:
    """prefill(S tokens) then decode token S must equal apply(S+1 tokens)."""

    @pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
    def test_prefill_decode_matches_full(self, family):
        S = 12
        if family == "dense":
            cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=16,
                              num_heads=4, num_kv_heads=2, d_ff=32, vocab_size=V,
                              qk_norm=True)
        elif family == "ssm":
            cfg = ModelConfig(name="t", family="ssm", num_layers=2, d_model=16,
                              num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=V,
                              attention="none",
                              ssm=SSMConfig(state_dim=4, head_dim=4, num_heads=4,
                                            conv_width=4, chunk=4))
        else:
            cfg = ModelConfig(name="t", family="hybrid", num_layers=2, d_model=16,
                              num_heads=4, num_kv_heads=2, d_ff=32, vocab_size=V,
                              ssm=SSMConfig(state_dim=4, head_dim=4, num_heads=4,
                                            conv_width=4, chunk=4))
        m = build_model(cfg)
        params = init_params(jax.random.PRNGKey(0), m.specs, jnp.float32)
        toks = _toks(2, S + 1)
        # full forward over S+1 tokens: logits at position S
        logits_full, _ = m.apply(params, {"tokens": toks}, remat="none")
        want = np.array(logits_full[:, -1])

        # prefill S, pad caches to S+1, decode token S
        _, caches = m.prefill(params, {"tokens": toks[:, :S]})

        def pad_to(c, target):
            def f(leaf, spec_len=target):
                # pad kv/seq axis (axis=2 after layer-stacking) for attn caches
                return leaf
            return c

        # pad attention caches along the sequence axis (L, B, S, ...) -> S+1
        def pad_leaf(path, leaf):
            return leaf

        caches = jax.tree_util.tree_map_with_path(
            lambda p, l: (
                jnp.pad(l, [(0, 0), (0, 0), (0, 1)] + [(0, 0)] * (l.ndim - 3))
                if any(getattr(k, "key", None) in ("k", "v") for k in p)
                else l
            ),
            caches,
        )
        logits_dec, _ = m.decode(params, caches, toks[:, S:], jnp.int32(S))
        got = np.array(logits_dec)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def _gqa_decode_inputs(num_kv_heads, B=2, S=16):
    """A small GQA decoder (8 query heads of 16) and one decode step's
    inputs with per-row positions, as continuous batching feeds it."""
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=8, num_kv_heads=num_kv_heads, head_dim=16,
                      d_ff=32, vocab_size=V, qk_norm=True)
    m = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), m.specs, jnp.float32)
    caches = init_cache(jax.random.PRNGKey(0), m.cache_specs(B, S), jnp.float32)
    leaves, tdef = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    caches = jax.tree.unflatten(
        tdef, [jax.random.normal(k, x.shape, x.dtype) for k, x in zip(keys, leaves)]
    )
    toks = _toks(B, 1, seed=3)
    pos = jnp.array([5, S - 1][:B], jnp.int32)
    return cfg, m, (params, caches, toks, pos)


class TestGroupedDecode:
    """Decode attention contracts each group of H // KV query heads against
    its K/V head as stored, with or without an activation mesh."""

    def test_decode_step_reads_kv_heads_unrepeated(self, monkeypatch):
        from repro.models import attention

        cfg, m, args = _gqa_decode_inputs(num_kv_heads=2)
        B, S = args[1]["attn"]["k"].shape[1:3]
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        repeated = re.compile(
            rf"broadcast_in_dim.*-> tensor<({B}x{S}x{H}x{hd}|{B}x{S}x{KV}x{H // KV}x{hd})x"
        )

        def lower_and_run():
            step = jax.jit(lambda *a: m.decode(*a))  # traced afresh
            hlo = step.lower(*args).as_text()
            logits, _ = step(*args)
            return hlo, np.asarray(logits)

        hlo, got = lower_and_run()
        assert not repeated.search(hlo)

        # the repeat form: K/V (the cache's and the token's own) copied to
        # every query head, one head a group
        grouped = attention._sdpa_decode
        monkeypatch.setattr(
            attention, "_sdpa_decode",
            lambda q, *kv, **kw: grouped(
                q, *(attention._repeat_kv(x, H) for x in kv[:4]), *kv[4:], **kw),
        )
        hlo_rep, want = lower_and_run()
        assert repeated.search(hlo_rep)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_head_sharded_mesh_matches_unsharded(self):
        """4 virtual devices on the model axis, parameters and caches placed
        by their logical axes: wq shards the H = 8 query heads 4 ways.  For
        KV = 2 each group of 4 heads spans two shards (K/V replicated, the
        cache sharded by position), for KV = 4 it stays on one (K/V heads
        sharded).  Either way the decode step's logits, and the full causal
        forward's, equal those without a mesh."""
        script = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import json, sys
            import numpy as np
            import jax
            sys.path.insert(0, os.environ["TESTS_DIR"])
            from test_models import _gqa_decode_inputs, _toks
            from repro.dist import sharding as shd
            from repro.models import logical_axes

            from jax.sharding import Mesh

            mesh = Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model"))

            def run(m, params, caches, toks, pos):
                # fresh functions: the mesh is read when they are traced
                dec = jax.jit(lambda *a: m.decode(*a))(params, caches, toks, pos)[0]
                fwd = jax.jit(m.apply)(params, {"tokens": _toks(2, 16, seed=4)})
                return [np.asarray(x) for x in jax.tree.leaves((dec, fwd))]

            out = {}
            for kv in (2, 4):
                _, m, (params, caches, toks, pos) = _gqa_decode_inputs(num_kv_heads=kv)
                plain = run(m, params, caches, toks, pos)
                B, S = caches["attn"]["k"].shape[1:3]
                params = jax.device_put(
                    params, shd.tree_shardings(params, logical_axes(m.specs), mesh))
                caches = jax.device_put(caches, shd.tree_shardings(
                    caches, logical_axes(m.cache_specs(B, S)), mesh))
                shd.set_activation_sharding(mesh)
                try:
                    sharded = run(m, params, caches, toks, pos)
                finally:
                    shd.set_activation_sharding(None)
                out[kv] = {
                    "wq": str(params["layers"]["attn"]["wq"].sharding.spec),
                    "max_diff": max(float(np.max(np.abs(a - b)))
                                    for a, b in zip(sharded, plain)),
                }
            print(json.dumps(out))
            """
        )
        here = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ, TESTS_DIR=here, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.join(os.path.dirname(here), "src"))
        res = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=600)
        assert res.returncode == 0, res.stderr[-3000:]
        out = json.loads(res.stdout.strip().splitlines()[-1])
        for kv in ("2", "4"):
            assert "'model'" in out[kv]["wq"], out
            assert out[kv]["max_diff"] < 2e-5, out
