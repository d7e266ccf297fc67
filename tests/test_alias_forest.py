"""Strategy-zoo closure: on-device alias construction + radix forests.

Gates for the two frozen-distribution variants (DESIGN.md §11):

* the device alias builder's induced per-category mass equals the target
  distribution (the ``table_mass`` oracle) on every edge-case family —
  zero-weight categories, single-category rows, K=1, non-pow2 K,
  denormal/huge weight ratios — and matches the host Vose builder's
  induced distribution (chi-square parity on real draws);
* the Pallas assembly route (interpret mode) is bit-identical to its
  pure-XLA twin;
* the radix-forest draw is *exactly* ``searchsorted(cdf, u, 'right')``
  (dense boundary sweep);
* the jaxpr gate: an ``alias_device`` refresh is a closed jaxpr — no
  host callback, no ``while_loop`` (the legacy serial builder's
  signature primitive).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from test_sampler_stats import CHI2_999, _chi2_stat

from repro.core import alias as core_alias
from repro.core import radix
from repro.core.api import sample_categorical
from repro.kernels.alias_build import build_alias_tables_device
from repro.kernels.alias_build.ref import build_alias_tables_ref, table_mass
from repro.sampling.distribution import Categorical


def _target(w):
    w = np.asarray(w, np.float64)
    tot = w.sum(axis=-1, keepdims=True)
    uni = np.full_like(w, 1.0 / w.shape[-1])
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.where(tot > 0, w / np.where(tot > 0, tot, 1.0), uni)


def _mass_err(w, prob, alias):
    return float(
        np.abs(table_mass(np.asarray(prob), np.asarray(alias)) - _target(w)).max()
    )


# ---------------------------------------------------------------------------
# Builder exactness: edge cases + host parity
# ---------------------------------------------------------------------------

def _edge_weights():
    rng = np.random.default_rng(7)
    cases = {
        "uniform": np.ones((3, 16), np.float32),
        "random_nonpow2": rng.uniform(0.01, 1.0, (4, 37)).astype(np.float32),
        "random_pow2": rng.uniform(0.01, 1.0, (4, 64)).astype(np.float32),
        "zero_categories": np.where(
            rng.uniform(size=(4, 23)) < 0.4, 0.0,
            rng.uniform(0.1, 1.0, (4, 23)),
        ).astype(np.float32),
        "single_category": np.eye(5, 11, dtype=np.float32),
        "K1": np.ones((3, 1), np.float32),
        "zero_row": np.zeros((2, 9), np.float32),
        "denormal_huge": np.stack([
            np.asarray([1e-38, 1.0, 1e30, 1e-30, 2.0, 1e-38, 3e20, 1.0],
                       np.float32),
            np.asarray([1e30, 1e30, 1e-38, 1e-38, 1e-38, 1e-38, 1e-38,
                        1e-38], np.float32),
        ]),
        "skewed_zipf": (1.0 / np.arange(1, 101, dtype=np.float32) ** 1.3)[
            None
        ].repeat(2, 0),
    }
    return cases.items()


@pytest.mark.parametrize("name,w", _edge_weights())
def test_device_build_mass_exact(name, w):
    """The device builder's induced per-category mass equals the target
    distribution to float32 rounding, for every edge-case family."""
    t = build_alias_tables_device(jnp.asarray(w))
    # zero rows degrade to uniform by contract — _target encodes that
    err = _mass_err(w, t.prob, t.alias)
    assert err < 5e-6, f"{name}: mass err {err:.2e}"
    prob = np.asarray(t.prob)
    ali = np.asarray(t.alias)
    assert ((prob >= 0.0) & (prob <= 1.0 + 1e-6)).all(), name
    assert ((ali >= 0) & (ali < w.shape[-1])).all(), name


@pytest.mark.parametrize("name,w", _edge_weights())
def test_device_build_matches_sequential_oracle(name, w):
    """The numpy pack-sweep oracle and the closed-form device build induce
    the same distribution (they may differ in which heavy funds which
    light only through float rounding of the residuals)."""
    t = build_alias_tables_device(jnp.asarray(w))
    rp, ra = build_alias_tables_ref(w)
    dev = table_mass(np.asarray(t.prob), np.asarray(t.alias))
    ref = table_mass(rp, ra)
    assert np.abs(dev - ref).max() < 5e-6, name


def test_device_host_builder_parity_chi2():
    """Draw parity: tables from the host Vose builder and the device
    builder feed the same two-uniform draw and must produce the same
    distribution (chi-square on real draws, same gate as the zoo)."""
    K, N = 20, 150_000
    rng = np.random.default_rng(5)
    probs = rng.dirichlet(np.full(K, 0.3))
    w = jnp.tile(jnp.asarray(probs, jnp.float32)[None], (N, 1))
    for builder in ("host", "device"):
        if builder == "host":
            tables = core_alias.build_alias_tables_host(w)
        else:
            tables = build_alias_tables_device(w)
        idx = np.asarray(
            core_alias.draw_alias_batch(tables, jax.random.PRNGKey(3))
        )
        counts = np.bincount(idx, minlength=K).astype(np.float64)
        stat, _ = _chi2_stat(counts, probs)
        assert stat < CHI2_999[19], f"{builder}: chi2={stat:.1f}"


def test_pallas_interpret_matches_xla_twin():
    """The tiled assembly kernel (interpret mode on CPU) matches the
    pure-XLA twin: identical alias indices, probabilities equal to
    float32 rounding (the blocked one-hot gather may reassociate)."""
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.uniform(0.0, 1.0, (10, 53)).astype(np.float32))
    w = w * (rng.uniform(size=(10, 53)) > 0.3)  # sprinkle zeros
    a = build_alias_tables_device(w, impl="xla")
    b = build_alias_tables_device(w, impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(a.alias), np.asarray(b.alias))
    np.testing.assert_allclose(
        np.asarray(a.prob), np.asarray(b.prob), rtol=0, atol=5e-6
    )


# ---------------------------------------------------------------------------
# Radix forest: exact draw + chi-square through the API
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 7, 257, 1000])
def test_radix_draw_is_exact_searchsorted(K):
    rng = np.random.default_rng(K)
    w = rng.uniform(0.0, 1.0, (1, K)).astype(np.float32)
    w[w < 0.2] = 0.0  # zero categories make empty cdf steps
    nu = 512
    u = np.linspace(0.0, 1.0, nu, endpoint=False).astype(np.float32)
    cdf, root = radix.build_radix_forest(jnp.tile(jnp.asarray(w), (nu, 1)))
    got = np.asarray(radix.draw_radix_forest(cdf, root, jnp.asarray(u)))
    row = np.asarray(cdf[0])
    want = np.minimum(np.searchsorted(row, u, side="right"), K - 1)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("method", ["alias_device", "radix_forest"])
def test_new_variants_chi2(method):
    K, N = 20, 150_000
    rng = np.random.default_rng(5)
    probs = rng.dirichlet(np.full(K, 0.3))
    w = jnp.tile(jnp.asarray(probs, jnp.float32)[None], (N, 1))
    idx = np.asarray(
        sample_categorical(w, key=jax.random.PRNGKey(1), method=method)
    )
    counts = np.bincount(idx, minlength=K).astype(np.float64)
    stat, _ = _chi2_stat(counts, probs)
    assert stat < CHI2_999[19], f"{method}: chi2={stat:.1f}"


# ---------------------------------------------------------------------------
# Jaxpr gate: the device refresh is a closed jaxpr
# ---------------------------------------------------------------------------

def _all_prims(closed_jaxpr):
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
    if hasattr(val, "jaxpr") and hasattr(val.jaxpr, "eqns"):
        out.append(val.jaxpr)
    elif hasattr(val, "eqns"):
        out.append(val)
    elif isinstance(val, (list, tuple)):
        for v in val:
            out.extend(_iter_jaxprs(v))
    return out


def test_alias_device_refresh_is_closed_jaxpr():
    """The acceptance gate: rebuilding alias tables from new weights in
    ``alias_device`` emits no host callback and no ``while`` (the legacy
    serial Vose builder's signature primitive) — so ``refreshed`` /
    ``refresh_from_factors`` composes with jit/scan/shard_map with zero
    host round-trips.  The legacy builder demonstrably does use while."""
    w = jnp.ones((4, 300), jnp.float32)
    dist = Categorical.from_weights(w, method="alias_device")
    jaxpr = jax.make_jaxpr(lambda ww: dist.refreshed(ww).state)(w)
    prims = _all_prims(jaxpr)
    assert not any("callback" in p for p in prims), prims
    assert "while" not in prims, prims
    assert not any("infeed" in p or "outfeed" in p for p in prims), prims
    # also sort-free: XLA's CPU sort is a scalar comparator loop that
    # would hand the build back to the host builder (DESIGN.md §11)
    assert "sort" not in prims, prims

    legacy = jax.make_jaxpr(core_alias.build_alias_tables)(w)
    assert "while" in _all_prims(legacy)


def test_radix_refresh_is_closed_jaxpr():
    w = jnp.ones((4, 300), jnp.float32)
    dist = Categorical.from_weights(w, method="radix_forest")
    prims = _all_prims(jax.make_jaxpr(lambda ww: dist.refreshed(ww).state)(w))
    assert not any("callback" in p for p in prims), prims
    assert "while" not in prims, prims


# ---------------------------------------------------------------------------
# TableCache digest memoization
# ---------------------------------------------------------------------------

def test_content_digest_memoized_per_instance(monkeypatch):
    """Repeated lookups on the same held matrix skip the reductions; a
    distinct instance (even with equal content) recomputes; changed
    content changes the digest."""
    from repro.sampling import tables

    w = jnp.asarray(np.random.default_rng(0).uniform(size=(8, 64)),
                    jnp.float32)
    d1 = tables.content_digest(w)
    assert d1 is not None

    def boom(_):
        raise AssertionError("digest recomputed for a memoized instance")

    monkeypatch.setattr(tables, "_digest_reductions", boom)
    assert tables.content_digest(w) == d1  # memo hit, no reduction
    monkeypatch.undo()

    w2 = jnp.asarray(np.asarray(w))  # same content, new instance
    assert tables.content_digest(w2) == d1  # recomputes, equal digest
    w3 = w.at[0, 0].add(1.0)
    assert tables.content_digest(w3) != d1


def test_sparse_word_proposal_alias_device_runs():
    """The in-graph word-proposal mode: same sweep, device-built tables."""
    from repro.lda import sparse as sp
    from repro.lda.corpus import synthesize_corpus
    from repro.lda.gibbs import init_state

    assert "alias_device" in sp.WORD_PROPOSALS
    corpus = synthesize_corpus(0, M=24, V=64, K=8, avg_len=16, max_len=24)
    st = init_state(jax.random.PRNGKey(1), corpus, K=8)
    cache = sp.SparseSweepCache()
    s2 = sp.gibbs_step_sparse(
        st, corpus, word_proposal="alias_device", cache=cache
    )
    assert int(s2.step) == int(st.step) + 1
