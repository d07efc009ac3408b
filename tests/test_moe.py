"""MoE layer: masked-local EP vs dense reference, capacity semantics,
multi-device shard_map equivalence (subprocess: 8 fake devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.configs.base import ModelConfig
from repro.models.moe import moe_apply, moe_reference, moe_specs, _capacity
from repro.models.params import init_params
from repro.runtime.sharding import ShardingPolicy, base_rules

POL = ShardingPolicy(rules=base_rules(False), mesh=None)


def _cfg(e=8, k=2, shared=0, slack=4.0):
    return ModelConfig(
        name="t", family="moe", d_model=32, n_experts=e, moe_top_k=k,
        moe_d_ff=64, d_ff=64, n_shared_experts=shared, capacity_slack=slack,
    )


@pytest.mark.parametrize("e,k,shared", [(4, 1, 0), (8, 2, 0), (8, 2, 1), (16, 4, 0)])
def test_moe_matches_dense_reference(e, k, shared, key):
    cfg = _cfg(e, k, shared)
    p = init_params(moe_specs(cfg, tp_hint=1), key)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    out, aux = moe_apply(cfg, POL, p, x)
    ref, aux_r = moe_reference(cfg, p, x)
    if shared:
        from repro.models.layers import mlp_apply

        gate = jax.nn.sigmoid((x @ p["shared_gate"]).astype(jnp.float32))
        ref = ref + mlp_apply(cfg, POL, p["shared"], x) * gate.astype(x.dtype)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert_allclose(float(aux), float(aux_r), rtol=1e-5)


def test_capacity_drops_tokens_when_tight(key):
    """With slack<1 some (token, expert) pairs must drop — output changes but
    stays finite (capacity-based load shedding)."""
    cfg = _cfg(slack=0.25)
    p = init_params(moe_specs(cfg, tp_hint=1), key)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    out, _ = moe_apply(cfg, POL, p, x)
    ref, _ = moe_reference(cfg, p, x)
    assert np.isfinite(np.asarray(out)).all()
    assert float(jnp.abs(out - ref).max()) > 1e-6, "expected drops under tight capacity"


@given(t=st.integers(1, 64), k=st.integers(1, 4), tp=st.sampled_from([1, 2, 4, 16]))
@settings(max_examples=20, deadline=None)
def test_capacity_formula_properties(t, k, tp):
    cfg = ModelConfig(name="t", n_experts=16, moe_top_k=k, capacity_slack=1.5)
    cap = _capacity(cfg, t, tp)
    assert cap >= k  # a single token's k choices on one shard always fit
    assert cap % 8 == 0  # TPU-aligned
    assert cap >= int(np.ceil(t * k / tp))  # >= expected load


@pytest.mark.parametrize("impl", ["psum", "a2a"])
def test_moe_sharded_equals_single_device(impl):
    code = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs.base import ModelConfig
        from repro.models.moe import moe_apply, moe_reference, moe_specs
        from repro.models.params import init_params
        from repro.runtime.sharding import ShardingPolicy, base_rules

        cfg = ModelConfig(name="t", family="moe", d_model=32, n_experts=8,
                          moe_top_k=2, moe_d_ff=64, d_ff=64, capacity_slack=8.0,
                          moe_impl="{impl}")
        key = jax.random.PRNGKey(0)
        p = init_params(moe_specs(cfg, tp_hint=4), key)
        x = jax.random.normal(key, (4, 16, cfg.d_model))
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
        pol = ShardingPolicy(rules=base_rules(False), mesh=mesh)
        out_sharded, aux_s = jax.jit(lambda p, x: moe_apply(cfg, pol, p, x))(p, x)
        ref, aux_r = moe_reference(cfg, p, x)
        np.testing.assert_allclose(np.asarray(out_sharded), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)
        print("MOE_SHARDED_OK")
        """
    )
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "MOE_SHARDED_OK" in r.stdout, r.stderr[-2000:]


def test_router_gates_renormalized(key):
    from repro.models.moe import _route

    cfg = _cfg(e=8, k=2)
    p = init_params(moe_specs(cfg, tp_hint=1), key)
    x = jax.random.normal(key, (32, cfg.d_model))
    gates, ids, probs = _route(cfg, p["router"], x)
    assert_allclose(np.asarray(gates.sum(-1)), np.ones(32), rtol=1e-5)
    assert (np.asarray(ids) < cfg.n_experts).all(), "padded experts must never be routed"


# ------------------------------------------------------------------ #
# held experts (DeepSeek-V3 routing, one chip's share)
# ------------------------------------------------------------------ #


def _held_cfg(**kw):
    base = dict(name="held", family="moe", d_model=32, n_experts=16, moe_top_k=4, moe_d_ff=24,
                d_ff=64, n_shared_experts=1, experts_held=4, expert_offset=4, moe_scale=2.5,
                dtype="float32")
    return ModelConfig(**{**base, **kw})


def test_sigmoid_routing_picks_by_score_plus_bias_and_weighs_by_score():
    """``moe.route`` against a selection written out by hand, with a bias
    large enough to change some picks: experts are picked by sigmoid score
    plus bias, weighted by the score alone, normalised, then scaled."""
    from repro.models.moe import route

    cfg = _held_cfg()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 32)).astype(np.float32)
    router = rng.normal(scale=32 ** -0.5, size=(32, 16)).astype(np.float32)
    bias = rng.normal(scale=0.3, size=(16,)).astype(np.float32)
    w, ids = route(cfg, {"router": jnp.asarray(router), "select_bias": jnp.asarray(bias)},
                   jnp.asarray(x))
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
    want_ids = np.argsort(-(scores + bias), axis=1, kind="stable")[:, :4]
    picked = np.take_along_axis(scores, want_ids, 1)
    want_w = picked / (picked.sum(1, keepdims=True) + 1e-20) * 2.5
    np.testing.assert_array_equal(np.sort(np.asarray(ids), 1), np.sort(want_ids, 1))
    order = np.argsort(np.asarray(ids), 1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1),
                               np.take_along_axis(want_w, np.argsort(want_ids, 1), 1), rtol=1e-5)
    by_score = np.argsort(-scores, axis=1, kind="stable")[:, :4]
    assert (np.sort(by_score, 1) != np.sort(want_ids, 1)).any(), "the bias changed no pick"


def test_a_held_layer_computes_only_its_experts_and_counts_their_pairs(key):
    """The held layer's output is the shared expert plus, for each token,
    the routing weight of each held expert it picked times that expert's
    MLP; its counters count those (token, held expert) pairs."""
    from repro.models.moe import held_moe_apply, held_moe_specs, route

    cfg = _held_cfg()
    p = init_params(held_moe_specs(cfg), key)
    p["select_bias"] = jax.random.normal(jax.random.PRNGKey(1), (16,)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, 32))
    live = jnp.arange(12).reshape(2, 6) % 5 != 0
    out, stats = held_moe_apply(cfg, POL, p, x, live)
    w, ids = route(cfg, p, x.reshape(12, 32))
    want = np.zeros((12, 32))
    pairs = np.zeros(4, int)
    for t in range(12):
        for j in range(4):
            e = int(ids[t, j]) - 4
            if 0 <= e < 4 and bool(live.reshape(-1)[t]):
                xe = np.asarray(x.reshape(12, 32)[t], np.float64)
                h = xe @ np.asarray(p["wg"][e]) * (1 / (1 + np.exp(-(xe @ np.asarray(p["wg"][e])))))
                h = h * (xe @ np.asarray(p["wu"][e]))
                want[t] += float(w[t, j]) * (h @ np.asarray(p["wd"][e]))
                pairs[e] += 1
    sh = p["shared"]
    xs = np.asarray(x.reshape(12, 32), np.float64)
    g = xs @ np.asarray(sh["wg"])
    want += (g / (1 + np.exp(-g)) * (xs @ np.asarray(sh["wu"]))) @ np.asarray(sh["wd"])
    assert_allclose(np.asarray(out).reshape(12, 32), want, rtol=2e-4, atol=2e-5)
    assert list(np.asarray(stats)) == [pairs.sum(), (pairs > 0).sum(), pairs.max()]
