"""Distributed LDA on an 8-host-device mesh (subprocess so XLA_FLAGS can't
leak): documents shard over 'data', phi replicates, counts all-reduce —
and the sweep matches the single-device sampler's dynamics.  Since the
shard_map rewrite the z-draw goes through the factored sampling plan with
counter RNG (see tests/test_sharded_sampler.py for the collective gates)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.lda import init_state, perplexity, synthesize_corpus
    from repro.lda.distributed import make_sharded_gibbs

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    K = 8
    corpus = synthesize_corpus(seed=0, M=96, V=120, K=K, avg_len=40, max_len=64)
    state = init_state(jax.random.PRNGKey(1), corpus, K)
    p0 = perplexity(state, corpus)
    place, step = make_sharded_gibbs(mesh, K=K, V=corpus.vocab_size)
    with mesh:
        state, docs, mask = place(state, corpus.docs, corpus.mask)
        for _ in range(15):
            state = step(state, docs, mask)
    from repro.lda import LDAState
    host = LDAState(*[jax.device_get(x) for x in state])
    p1 = perplexity(host, corpus)
    theta_sharding = state.theta.sharding.spec
    phi_sharding = state.phi.sharding.spec
    print(json.dumps({
        "p0": float(p0), "p1": float(p1),
        "theta_spec": str(theta_sharding), "phi_spec": str(phi_sharding),
        "phi_replicated": state.phi.sharding.is_fully_replicated,
        "theta_nshards": len(set(d.id for d in state.theta.devices())),
    }))
    """
)


@pytest.mark.slow
def test_distributed_gibbs_8_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["p1"] < 0.8 * res["p0"], res
    assert "data" in res["theta_spec"], res
    assert res["theta_nshards"] == 8  # docs spread across all devices
    assert res["phi_replicated"] is True, res  # spec P() or P(None, None)
