"""In-mesh federated retrieval: federated == centralized top-k (the
correctness invariant of the paper's Alg. 1 merge), quorum masking."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.core.retrieval import federated_topk
from repro.kernels.retrieval_topk.ref import retrieval_topk_ref


def test_federated_equals_centralized_single_device(key):
    q = jax.random.normal(key, (4, 32))
    c = jax.random.normal(jax.random.fold_in(key, 1), (128, 32))
    s_f, i_f, _ = federated_topk(q, c, m_local=8, n_global=8, mesh=None)
    s_c, i_c = retrieval_topk_ref(q, c, 8)
    assert_allclose(np.asarray(s_f), np.asarray(s_c), rtol=1e-5)
    assert (np.asarray(i_f) == np.asarray(i_c)).all()


@given(seed=st.integers(0, 500), m=st.integers(4, 16))
@settings(max_examples=10, deadline=None)
def test_federated_merge_property(seed, m):
    """With m_local >= n_global, merging per-shard top-m must equal global
    top-n (scores), for any shard split."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(3, 16)).astype(np.float32)
    c = rng.normal(size=(64, 16)).astype(np.float32)
    n_global = min(m, 8)
    full = q @ c.T
    expect = np.sort(full, axis=1)[:, -n_global:][:, ::-1]
    # simulate the shard merge on host (mesh-free path + manual shards)
    shards = np.split(c, 4)
    cand_s = []
    for sh in shards:
        s = q @ sh.T
        cand_s.append(np.sort(s, 1)[:, -m:])
    merged = np.sort(np.concatenate(cand_s, 1), 1)[:, -n_global:][:, ::-1]
    assert_allclose(merged, expect, rtol=1e-5)


def _spawn_multidevice_check():
    """Runs the sharded federated_topk on 8 fake devices in a subprocess
    (this process is pinned to 1 device for the smoke tests)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.retrieval import federated_topk
        from repro.kernels.retrieval_topk.ref import retrieval_topk_ref
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        k = jax.random.PRNGKey(0)
        q = jax.random.normal(k, (4, 32))
        c = jax.random.normal(jax.random.fold_in(k, 1), (128, 32))
        s_f, i_f, p_f = federated_topk(q, c, m_local=8, n_global=8, mesh=mesh)
        s_c, i_c = retrieval_topk_ref(q, c, 8)
        np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_c), rtol=1e-5)
        assert (np.asarray(i_f) == np.asarray(i_c)).all(), "indices differ"
        assert (np.asarray(p_f) == np.asarray(i_f) // 32).all(), "provider attribution"
        # quorum: kill provider 0 -> its chunks must vanish
        alive = jnp.array([False, True, True, True])
        s_q, i_q, p_q = federated_topk(q, c, m_local=8, n_global=8, mesh=mesh, alive=alive)
        assert (np.asarray(p_q) != 0).all(), "dead provider leaked chunks"
        print("MULTIDEVICE_OK")
        """
    )
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def test_federated_topk_sharded_8dev():
    r = _spawn_multidevice_check()
    assert "MULTIDEVICE_OK" in r.stdout, r.stderr[-2000:]
