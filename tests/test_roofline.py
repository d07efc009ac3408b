"""Roofline extraction: HLO parsers + term math on synthetic inputs, and
the dist_decode serving path vs the monolithic oracle."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch.roofline import (
    Roofline,
    parse_collective_bytes,
    parse_convert_bytes,
    parse_dus_bytes,
)

HLO = """
HloModule test
ENTRY main {
  %p0 = bf16[128,256]{1,0} parameter(0)
  %ag = bf16[2048,256]{1,0} all-gather(%p0), replica_groups={}
  %cv = f32[2048,256]{1,0} convert(%ag)
  %ar = f32[2048,256]{1,0} all-reduce(%cv), to_apply=%add
  %rs = f32[128,256]{1,0} reduce-scatter(%ar), to_apply=%add
  %a2a = f32[128,256]{1,0} all-to-all(%rs)
  %dus = f32[2048,256]{1,0} dynamic-update-slice(%ar, %rs, %c0, %c0)
  ROOT %cp = f32[128,256]{1,0} collective-permute(%a2a)
}
"""


def test_parse_collective_bytes_per_kind():
    out = parse_collective_bytes(HLO)
    assert out["all-gather"] == 128 * 256 * 2  # operand bytes (bf16 p0)
    assert out["all-reduce"] == 2048 * 256 * 4
    assert out["reduce-scatter"] == 2048 * 256 * 4
    assert out["all-to-all"] == 128 * 256 * 4
    assert out["collective-permute"] == 128 * 256 * 4
    assert out["collective_count"] == 5


def test_parse_convert_bytes():
    # bf16 -> f32 convert of 2048x256: 4B out + 2B in per elem
    assert parse_convert_bytes(HLO) == 2048 * 256 * (4 + 2)


def test_parse_dus_bytes():
    assert parse_dus_bytes(HLO) == 2048 * 256 * 4


def test_roofline_terms_math():
    r = Roofline(
        arch="x", shape="train_4k", mesh="single", n_chips=256,
        hlo_flops=256 * 197e12,  # exactly 1s of compute
        hlo_bytes=256 * 819e9 * 0.5,  # 0.5s memory
        collective_bytes=256 * 49.5e9 * 2.0,  # 2s collective
        collective_detail={}, model_flops=256 * 197e12 * 0.8,
        memory_per_device=1,
    )
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(0.5)
    assert r.collective_s == pytest.approx(2.0)
    assert r.dominant == "collective"
    assert r.step_bound_s == pytest.approx(2.0)
    assert r.mfu_bound == pytest.approx(0.8 / 2.0)
    assert r.useful_flops_frac == pytest.approx(0.8)


def test_dist_decode_matches_oracle_8dev():
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.serving.dist_decode import dist_decode_attention
        from repro.kernels.decode_attention.ref import decode_attention_ref

        mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
        k = jax.random.PRNGKey(0)
        b, s, h, kv, dh = 2, 128, 8, 4, 32
        q = jax.random.normal(k, (b, h, dh))
        kc = jax.random.normal(jax.random.fold_in(k, 1), (b, s, kv, dh))
        vc = jax.random.normal(jax.random.fold_in(k, 2), (b, s, kv, dh))
        lens = jnp.array([100, 77])
        out = jax.jit(lambda *a: dist_decode_attention(*a, mesh=mesh))(q, kc, vc, lens)
        ref = decode_attention_ref(q, kc, vc, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref, np.float32),
                                   rtol=2e-5, atol=2e-5)
        print("DIST_DECODE_OK")
        """
    )
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "DIST_DECODE_OK" in r.stdout, r.stderr[-2000:]
