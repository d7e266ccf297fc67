"""Truncated decode sampling: the transforms layer vs the sorted oracle.

Covers the ISSUE 5 acceptance gates:

* fused top-k/top-p/min-p masks agree EXACTLY with the sorted-reference
  oracle across K in {8, 257, 4096} and a W sweep (continuous weights:
  the 32-step value bisection lands inside the float32 spacing at the
  boundary), and end-to-end draws agree by chi-squared at p > 1e-3;
* a jaxpr gate proving the fused path emits no sort-family primitive and
  never materializes a (B, K) sorted copy (while the oracle demonstrably
  does sort);
* per-row heterogeneous parameters ride one compiled executable;
* sharded transform invariance on 8 virtual devices (subprocess);
* the CI perf-regression gate (benchmarks/check_regression.py) fails on
  an injected 2x slowdown;
* TuningCache v4 round-trips v1/v2/v3 files and buckets truncated
  workloads separately.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import sampling
from repro.sampling import reference as sref
from repro.sampling import transforms as tr
from repro.sampling.transforms import MinP, Temperature, TopK, TopP

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import check_regression  # noqa: E402  (the benchmarks/ script under test)


def chi2_crit_999(dof: int) -> float:
    """99.9th-percentile chi-square critical value (Wilson-Hilferty
    approximation, <1% error for dof >= 3) — stat below this means the
    goodness-of-fit p-value exceeds 1e-3."""
    z = 3.0902  # Phi^-1(0.999)
    return dof * (1.0 - 2.0 / (9.0 * dof) + z * np.sqrt(2.0 / (9.0 * dof))) ** 3


KS = (8, 257, 4096)
WS = (8, 32)


def _chain_grid(K):
    return [
        ("topk", tr.chain(top_k=max(2, K // 3))),
        ("topp", tr.chain(top_p=0.7)),
        ("minp", tr.chain(min_p=0.05)),
        ("kpm", tr.chain(top_k=max(4, K // 2), top_p=0.9, min_p=0.01)),
    ]


# ---------------------------------------------------------------------------
# Exact mask agreement: threshold path vs sorted oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K", KS)
def test_mask_matches_sorted_oracle(K):
    rng = np.random.default_rng(K)
    B = 24
    w = jnp.array(rng.uniform(0.01, 1.0, (B, K)).astype(np.float32))
    for name, chain in _chain_grid(K):
        fused = np.array(tr.apply(w, chain) > 0)
        oracle = np.array(sref.truncate_sorted(w, chain) > 0)
        assert (fused == oracle).all(), (
            f"{name} K={K}: {int((fused != oracle).sum())} mask mismatches"
        )


def test_mask_matches_on_peaked_softmax_weights():
    """Logit-shaped weights (12 orders of magnitude of dynamic range) —
    the regime the bisection must stay exact in."""
    rng = np.random.default_rng(7)
    B, K = 16, 4096
    logits = jnp.array(rng.normal(0, 4.0, (B, K)).astype(np.float32))
    w = sampling.logits_to_weights(logits, 0.7)
    for name, chain in _chain_grid(K):
        fused = np.array(tr.apply(w, chain) > 0)
        oracle = np.array(sref.truncate_sorted(w, chain) > 0)
        assert (fused == oracle).all(), name


def test_sequential_composition_top_k_then_top_p():
    """top-p must operate on the top-k survivors (sequential semantics),
    not the full distribution."""
    w = jnp.array([[0.4, 0.3, 0.2, 0.05, 0.03, 0.02]], jnp.float32)
    # top-k=3 keeps {0.4, 0.3, 0.2} (mass 0.9); top-p=0.5 of THAT mass
    # (0.45) keeps {0.4, 0.3} — against the full total it would keep a
    # different set
    chain = tr.chain(top_k=3, top_p=0.5)
    mask = np.array(tr.apply(w, chain) > 0)[0]
    assert mask.tolist() == [True, True, False, False, False, False]
    oracle = np.array(sref.truncate_sorted(w, chain) > 0)[0]
    assert (mask == oracle).all()


def test_disabled_stages_pass_through():
    rng = np.random.default_rng(0)
    w = jnp.array(rng.uniform(0.1, 1.0, (6, 33)).astype(np.float32))
    chain = tr.chain(top_k=0, top_p=1.0, min_p=0.0)
    np.testing.assert_array_equal(np.array(tr.apply(w, chain)), np.array(w))


def test_temperature_in_chain_rejected_on_weights():
    w = jnp.ones((2, 8), jnp.float32)
    with pytest.raises(ValueError, match="Temperature"):
        tr.thresholds(w, (Temperature(0.5),))


def test_signature_and_canonical_params():
    assert tr.signature(tr.chain(top_k=5, top_p=0.9, min_p=0.1)) == "kpm"
    assert tr.signature(tr.chain(temperature=0.5, top_p=0.9)) == "tp"
    assert tr.signature(None) == ""
    kpm = tr.canonical_params(tr.chain(top_p=0.9), B=4)
    assert kpm.shape == (4, 3)
    np.testing.assert_allclose(np.array(kpm[0]), [0.0, 0.9, 0.0])
    # non-canonical order (top-p before top-k) has no kernel param block
    assert tr.canonical_params((TopP(0.9), TopK(5)), B=4) is None
    # ... but the XLA twin still handles it sequentially
    rng = np.random.default_rng(1)
    w = jnp.array(rng.uniform(0.01, 1.0, (8, 64)).astype(np.float32))
    fused = np.array(tr.apply(w, (TopP(0.9), TopK(5))) > 0)
    oracle = np.array(sref.truncate_sorted(w, (TopP(0.9), TopK(5))) > 0)
    assert (fused == oracle).all()


def test_transforms_are_pytrees_with_traced_params():
    chain = tr.chain(top_k=5, top_p=0.9)
    leaves, treedef = jax.tree_util.tree_flatten(chain)
    assert leaves == [5, 0.9]
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(rebuilt[0], TopK) and isinstance(rebuilt[1], TopP)


# ---------------------------------------------------------------------------
# Chi-squared draw agreement vs the oracle distribution (p > 1e-3)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["two_level", "kernel"])
@pytest.mark.parametrize(
    "K,W,chain_kw",
    [
        (8, 8, dict(top_k=5)),
        (8, 8, dict(top_p=0.8)),
        (257, 8, dict(top_k=24, top_p=0.9)),
        (257, 32, dict(min_p=0.02)),
        (4096, 32, dict(top_k=48, top_p=0.95)),
    ],
)
def test_truncated_draws_match_oracle_chi2(method, K, W, chain_kw):
    """One distribution row replicated N times, drawn through the full
    sample_logits path; counts vs the oracle's renormalized probs."""
    N = 60_000 if K <= 257 else 30_000
    rng = np.random.default_rng(K + W)
    logits_row = rng.normal(0, 2.0, (K,)).astype(np.float32)
    logits = jnp.tile(jnp.array(logits_row)[None], (N, 1))
    chain = tr.chain(**chain_kw)
    p = sampling.plan((N, K), method=method, W=W, transforms="kpm")
    idx = np.array(
        p.sample_logits(logits, jax.random.PRNGKey(3), temperature=0.9,
                        transforms=chain)
    )
    probs = np.array(
        sref.truncated_probs(
            sampling.logits_to_weights(jnp.array(logits_row)[None], 0.9),
            chain,
        )
    )[0]
    assert np.all(probs[idx] > 0), "draw outside the truncated support"
    counts = np.bincount(idx, minlength=K).astype(np.float64)
    expected = probs * N
    m = expected > 5
    dof = int(m.sum()) - 1
    stat = float(((counts[m] - expected[m]) ** 2 / expected[m]).sum())
    assert dof >= 2, "degenerate support"
    assert stat < chi2_crit_999(dof), (
        f"{method} K={K} {chain_kw}: chi2={stat:.1f} dof={dof}"
    )


def test_multi_draw_and_from_logits_respect_truncation():
    rng = np.random.default_rng(5)
    B, K = 32, 128
    logits = jnp.array(rng.normal(0, 2.0, (B, K)).astype(np.float32))
    chain = tr.chain(top_k=9)
    support = np.array(tr.apply_to_logits(chain, logits, 0.8) > 0)
    # plan path, multi-draw
    p = sampling.plan((B, K), method="two_level", W=8, transforms="k")
    multi = np.array(
        p.sample_logits(logits, jax.random.PRNGKey(0), temperature=0.8,
                        num_samples=5, transforms=chain)
    )
    assert multi.shape == (5, B)
    for s in range(5):
        assert support[np.arange(B), multi[s]].all()
    # build path: truncation baked into the table
    dist = sampling.Categorical.from_logits(
        logits, temperature=0.8, method="fenwick", W=8, transforms=chain
    )
    idx = np.array(dist.draw(key=jax.random.PRNGKey(1)))
    assert support[np.arange(B), idx].all()
    # gumbel stays in logit space but honors the same support
    pg = sampling.plan((B, K), method="gumbel", transforms="k")
    idxg = np.array(
        pg.sample_logits(logits, jax.random.PRNGKey(2), temperature=0.8,
                         transforms=chain)
    )
    assert support[np.arange(B), idxg].all()


# ---------------------------------------------------------------------------
# Per-row heterogeneous params: one executable, per-request truncation
# ---------------------------------------------------------------------------


def test_per_row_heterogeneous_params():
    rng = np.random.default_rng(11)
    B, K = 48, 256
    logits = jnp.array(rng.normal(0, 2.0, (B, K)).astype(np.float32))
    ks = jnp.array(rng.integers(1, 30, B).astype(np.float32))
    ps = jnp.array(rng.uniform(0.5, 1.0, B).astype(np.float32))
    temps = jnp.array(rng.uniform(0.5, 1.5, B).astype(np.float32))
    chain = tr.chain(temperature=temps, top_k=ks, top_p=ps)
    support = np.array(tr.apply_to_logits(chain, logits) > 0)
    # row i's support honors row i's own (k, p): spot-check the count cap
    w = np.array(tr.apply_to_logits((Temperature(temps),), logits))
    for b in range(0, B, 7):
        assert support[b].sum() <= int(ks[b])
    for method in ("two_level", "kernel"):
        p = sampling.plan((B, K), method=method, W=16, transforms="kpm")
        idx = np.array(
            p.sample_logits(logits, jax.random.PRNGKey(4), transforms=chain)
        )
        assert support[np.arange(B), idx].all(), method
    assert w.shape == (B, K)


def test_one_executable_serves_different_param_values():
    """Transform parameters are traced leaves: changing p must NOT
    retrace the jitted step."""
    traces = []
    B, K = 16, 64

    @jax.jit
    def step(logits, key, chain):
        traces.append(1)  # runs at trace time only
        p = sampling.plan((B, K), method="two_level", W=8, transforms="kpm")
        return p.sample_logits(logits, key, temperature=0.8, transforms=chain)

    rng = np.random.default_rng(0)
    logits = jnp.array(rng.normal(0, 2.0, (B, K)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    step(logits, key, tr.chain(top_k=5, top_p=0.9, min_p=0.01))
    n0 = len(traces)
    step(logits, key, tr.chain(top_k=11, top_p=0.7, min_p=0.05))
    step(logits, key, tr.chain(top_k=3, top_p=0.95, min_p=0.2))
    assert len(traces) == n0, "param value change retraced the step"


def test_sampling_params_defaults_from_configs():
    from repro.configs import gemma2_9b, llama3_8b, qwen3_4b
    from repro.serve.engine import default_sampling_params

    for mod, expect in (
        (llama3_8b, dict(top_k=0, top_p=0.9, min_p=0.0)),
        (gemma2_9b, dict(top_k=64, top_p=0.95, min_p=0.0)),
        (qwen3_4b, dict(top_k=20, top_p=0.95, min_p=0.0)),
    ):
        sp = default_sampling_params(mod.CONFIG)
        assert sp is not None, mod.__name__
        assert (sp.top_k, sp.top_p, sp.min_p) == (
            expect["top_k"], expect["top_p"], expect["min_p"]
        )
        assert sp.temperature is None  # defers to the engine argument
        # the chain is canonical, so the fused kernel path applies
        assert tr.canonical_params(sp.transforms(), B=4) is not None
    # a non-truncating config keeps the legacy fast path
    from repro.configs.base import ModelConfig

    plain = ModelConfig(
        name="t", family="dense", num_layers=1, d_model=8, num_heads=1,
        num_kv_heads=1, d_ff=16, vocab_size=32,
    )
    assert default_sampling_params(plain) is None


# ---------------------------------------------------------------------------
# Jaxpr gates: no sort-family primitive, no (B, K) sorted copy
# ---------------------------------------------------------------------------

SORT_PRIMS = {"sort", "top_k", "approx_top_k", "partial_sort"}


def _all_prims(closed_jaxpr):
    """Primitive names at every nesting depth (call/closed sub-jaxprs) —
    primitive-level matching, not substrings (scatter params legitimately
    contain the string 'sorted')."""
    acc = set()

    def walk(jx):
        for eqn in jx.eqns:
            acc.add(eqn.primitive.name)
            for val in eqn.params.values():
                for item in _iter_jaxprs(val):
                    walk(item)

    walk(closed_jaxpr.jaxpr)
    return acc


def _iter_jaxprs(val):
    out = []
    if hasattr(val, "jaxpr"):          # ClosedJaxpr
        out.append(val.jaxpr)
    elif hasattr(val, "eqns"):         # Jaxpr
        out.append(val)
    elif isinstance(val, (list, tuple)):
        for v in val:
            out.extend(_iter_jaxprs(v))
    return out


def test_fused_path_jaxpr_has_no_sort():
    """The acceptance gate: the fused truncated draw contains no
    sort-family primitive at any nesting depth — while the oracle's
    jaxpr demonstrably does."""
    from repro.kernels.butterfly_sample import ops as kops

    B, K = 16, 512
    w = jnp.ones((B, K), jnp.float32)
    u = jnp.full((B,), 0.5, jnp.float32)
    kpm = tr.canonical_params(tr.chain(top_k=50, top_p=0.9, min_p=0.01), B)
    jx = jax.make_jaxpr(
        lambda w, u, p: kops.butterfly_sample_truncated(w, u, p, W=16)
    )(w, u, kpm)
    prims = _all_prims(jx)
    assert not (prims & SORT_PRIMS), prims & SORT_PRIMS
    # the XLA threshold twin is equally sort-free
    jx2 = jax.make_jaxpr(lambda w: tr.thresholds_from_params(w, kpm))(w)
    assert not (_all_prims(jx2) & SORT_PRIMS)
    # sanity: the sorted-reference oracle DOES sort
    jx3 = jax.make_jaxpr(
        lambda w: sref.truncate_sorted(w, tr.chain(top_k=50))
    )(w)
    assert "sort" in _all_prims(jx3)


def test_fused_path_materializes_no_sorted_copy():
    """Beyond 'no sort primitive': the fused route's only full-size
    (B-, K-shaped) intermediates are the weight pad itself — there is no
    second (B, K) buffer a sorted/reordered copy could live in.  The
    two-pass vocab-scale route is allowed its block-sum state (K/W wide),
    still never a (B, K) copy."""
    from repro.kernels.butterfly_sample import ops as kops

    B, K = 16, 512
    w = jnp.ones((B, K), jnp.float32)
    u = jnp.full((B,), 0.5, jnp.float32)
    kpm = tr.canonical_params(tr.chain(top_k=50, top_p=0.9), B)
    jx = jax.make_jaxpr(
        lambda w, u, p: kops.butterfly_sample_truncated(w, u, p, W=16)
    )(w, u, kpm)
    big = [
        eqn
        for eqn in jx.jaxpr.eqns
        for ov in eqn.outvars
        if getattr(ov.aval, "shape", ()) and ov.aval.shape[-1] >= K
        and len(ov.aval.shape) == 2 and ov.aval.shape[0] >= B
    ]
    # the pad of the weights (and nothing else) may be (B', K')-shaped
    assert len(big) <= 1, [str(e.primitive) for e in big]
    for eqn in big:
        assert eqn.primitive.name == "pad", eqn.primitive.name


# ---------------------------------------------------------------------------
# Sharded transform invariance (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------

SHARD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro import sampling
    from repro.sampling import transforms as tr

    out = {}
    r = np.random.default_rng(2)
    B, K = 64, 96
    logits = jnp.array(r.normal(0, 2, (B, K)).astype(np.float32))
    key = jax.random.PRNGKey(7)
    ks = jnp.array(r.integers(2, 20, B).astype(np.float32))
    chain = tr.chain(top_k=ks, top_p=0.9)
    support = np.array(tr.apply_to_logits(chain, logits, 0.8) > 0)

    for method in ("two_level", "kernel"):
        draws = {}
        for n in (1, 2, 8):
            mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
            p = sampling.plan((B, K), method=method, W=8, mesh=mesh,
                              transforms="kpm")
            zs = sampling.sharded.place_rows(mesh, logits)
            tok = np.array(p.sample_logits(zs, key, temperature=0.8,
                                           transforms=chain))
            assert support[np.arange(B), tok].all(), (method, n)
            draws[n] = tok.tolist()
        out[f"invariant_{method}"] = draws[1] == draws[2] == draws[8]

    # collectives gate on the truncated sharded path (primitive names,
    # not substrings — scatter params contain 'sorted')
    mesh8 = Mesh(np.array(jax.devices()), ("data",))
    p = sampling.plan((B, K), method="two_level", W=8, mesh=mesh8,
                      transforms="kpm")
    jx = jax.make_jaxpr(
        lambda z, k: p.sample_logits(z, k, temperature=0.8, transforms=chain)
    )(logits, key)
    prims = set()
    def walk(j):
        for e in j.eqns:
            prims.add(e.primitive.name)
            for v in e.params.values():
                for item in ([v] if hasattr(v, "eqns") else
                             [v.jaxpr] if hasattr(v, "jaxpr") else []):
                    walk(item)
    walk(jx.jaxpr)
    out["collectives"] = sorted(
        prims & {"all_gather", "all_to_all", "ppermute", "psum"}
    )
    out["sorts"] = sorted(prims & {"sort", "top_k", "approx_top_k"})
    print(json.dumps(out))
    """
)


@pytest.mark.slow
def test_sharded_transforms_8_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SHARD_SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["invariant_two_level"], res
    assert res["invariant_kernel"], res
    assert res["collectives"] == [], res
    assert res["sorts"] == [], res


# ---------------------------------------------------------------------------
# CI perf-regression gate (benchmarks/check_regression.py)
# ---------------------------------------------------------------------------


def _bench_blob(times: dict) -> dict:
    records = [
        {"backend": "cpu", "B": B, "K": K, "W": 32, "draws": 1,
         "dtype": "float32", "method": m, "us": us, "devices": dev}
        for (m, B, K, dev), us in times.items()
    ]
    return {"records": records}


BASE_TIMES = {
    ("two_level", 1024, 256, 1): 100.0,
    ("prefix", 1024, 256, 1): 80.0,
    ("trunc_fused", 256, 4096, 1): 500.0,
    ("two_level", 256, 256, 8): 120.0,
}


class TestCheckRegression:
    def _write(self, tmp_path, name, times):
        path = tmp_path / name
        path.write_text(json.dumps(_bench_blob(times)))
        return str(path)

    def test_ok_within_threshold(self, tmp_path):
        base = self._write(tmp_path, "base.json", BASE_TIMES)
        fresh = self._write(
            tmp_path, "fresh.json",
            {k: v * 1.2 for k, v in BASE_TIMES.items()},
        )
        assert check_regression.main([base, fresh]) == 0

    def test_injected_2x_slowdown_fails(self, tmp_path):
        """The acceptance gate: a 2x regression in any tracked row must
        fail the job."""
        slowed = dict(BASE_TIMES)
        slowed[("two_level", 1024, 256, 1)] *= 2.0
        base = self._write(tmp_path, "base.json", BASE_TIMES)
        fresh = self._write(tmp_path, "fresh.json", slowed)
        assert check_regression.main([base, fresh]) == 1

    def test_rows_match_on_method_shape_devices(self, tmp_path):
        """A 2x-slower row under a DIFFERENT key (new shape, new device
        count) is 'new', not a regression."""
        fresh_times = dict(BASE_TIMES)
        fresh_times[("two_level", 2048, 256, 1)] = 1e6   # new shape
        fresh_times[("two_level", 256, 256, 2)] = 1e6    # new topology
        base = self._write(tmp_path, "base.json", BASE_TIMES)
        fresh = self._write(tmp_path, "fresh.json", fresh_times)
        assert check_regression.main([base, fresh]) == 0

    def test_retired_rows_do_not_fail(self, tmp_path):
        fresh_times = {
            k: v for k, v in BASE_TIMES.items() if k[0] != "prefix"
        }
        base = self._write(tmp_path, "base.json", BASE_TIMES)
        fresh = self._write(tmp_path, "fresh.json", fresh_times)
        assert check_regression.main([base, fresh]) == 0

    def test_median_over_duplicate_keys(self, tmp_path):
        blob = _bench_blob({("two_level", 64, 64, 1): 10.0})
        blob["records"] += [
            dict(blob["records"][0], us=30.0),
            dict(blob["records"][0], us=20.0),
        ]
        path = tmp_path / "dup.json"
        path.write_text(json.dumps(blob))
        loaded = check_regression.load_rows(str(path))
        assert loaded[("two_level", 64, 64, 32, 1)] == 20.0

    def test_markdown_table_and_summary(self, tmp_path):
        slowed = dict(BASE_TIMES)
        slowed[("trunc_fused", 256, 4096, 1)] *= 3.0
        base = self._write(tmp_path, "base.json", BASE_TIMES)
        fresh = self._write(tmp_path, "fresh.json", slowed)
        summary = tmp_path / "summary.md"
        rc = check_regression.main(
            [base, fresh, "--summary", str(summary)]
        )
        assert rc == 1
        text = summary.read_text()
        assert "REGRESSED" in text and "trunc_fused" in text
        assert "| 3.00x |" in text

    def test_unusable_comparison_is_distinct_error(self, tmp_path):
        base = self._write(tmp_path, "base.json", BASE_TIMES)
        missing = str(tmp_path / "nope.json")
        assert check_regression.main([base, missing]) == 2
        empty = self._write(tmp_path, "empty.json", {})
        assert check_regression.main([base, empty]) == 2

    def test_committed_baselines_have_tracked_rows(self):
        """The real committed baselines must load and track rows —
        otherwise the CI gate silently gates nothing."""
        for name, floor in (
            ("BENCH_sampler.json", 4),
            ("BENCH_sampler_shard.json", 3),
        ):
            rows = check_regression.load_rows(os.path.join(REPO, name))
            assert len(rows) >= floor, name
        single = check_regression.load_rows(
            os.path.join(REPO, "BENCH_sampler.json")
        )
        assert any(k[0] == "trunc_fused" for k in single), (
            "decode rows missing from the committed baseline"
        )


# ---------------------------------------------------------------------------
# Truncated decode in the plan: the fused choice and the memo key
# ---------------------------------------------------------------------------


class TestAutotuneV4:
    def test_tpu_model_prefers_fused_truncated_at_vocab_scale(self):
        p = sampling.plan(
            (256, 131072), method="auto", backend="tpu", has_key=True,
            transforms="kpm",
        )
        assert p.method == "kernel_trunc", p
        # the untruncated workload keeps the plain fused kernel
        plain = sampling.plan(
            (256, 131072), method="auto", backend="tpu", has_key=True
        )
        assert plain.method == "kernel"

    def test_plan_memo_distinguishes_transform_signatures(self):
        sampling.reset_plans()
        p1 = sampling.plan((32, 256), method="two_level", W=8)
        p2 = sampling.plan((32, 256), method="two_level", W=8,
                           transforms="kpm")
        p3 = sampling.plan((32, 256), method="two_level", W=8,
                           transforms=tr.chain(top_k=5, top_p=0.9,
                                               min_p=0.1))
        assert p1 is not p2
        assert p2 is p3  # chain normalizes to its signature
        assert p2.transforms == "kpm"
        assert p1.transforms == ""
