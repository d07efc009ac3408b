"""Compile rehearsals for one TPU v5e chip, made without the chip.

The TPU compiler is installed beside the CPU backend, so the serving and
retrieval kernels can be compiled for a *described* v5e at the published
widths of qwen3-0.6b.  This catches what interpret mode cannot: tiling the
chip refuses, fast-memory overruns, programs that do not fit.  Each test
asserts the kernel is really in the program (``tpu_custom_call``), which
also proves ``repro.kernels.on_backend`` picks the compiled kernel for a
TPU target from a CPU process.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.chunked_prefill.kernel import mixed_prefill_attention_pallas
from repro.kernels.decode_attention.kernel import paged_decode_attention_pallas
from repro.kernels.retrieval_topk.kernel import retrieval_topk_pallas
from repro.models import lm as LM
from repro.models.params import init_params
from repro.runtime.sharding import ShardingPolicy, base_rules

# qwen3-0.6b attention geometry
H, KV, DH = 16, 8, 128
BS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A chip compile written to the persistent cache cannot be read back
    without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("w,n_t", [
    pytest.param(1, 33, id="1"),
    pytest.param(256, 33, id="256"),
    pytest.param(256, 160, id="256-160"),  # the benchmark cells' table width
])
def test_mixed_prefill_kernel_compiles_for_v5e(one_chip, w, n_t):
    b, n_pool = 8, 8 * n_t + 1
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    text = _compiled_text(
        mixed_prefill_attention_pallas,
        s((b, w, H, DH), jnp.bfloat16),
        s((n_pool, BS, KV, DH), jnp.bfloat16),
        s((n_pool, BS, KV, DH), jnp.bfloat16),
        s((b, n_t), jnp.int32),
        s((b, 4), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("h,n_t", [
    pytest.param(H, 33, id="0.6b-33"),
    pytest.param(H, 160, id="0.6b-160"),  # the benchmark cells' table width
    pytest.param(32, 160, id="4b-160"),  # qwen3-4b: 32 query heads over the same 8 KV heads
])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, h, n_t):
    """The live-block walk (pool left in HBM, blocks copied by DMA into
    double-buffered VMEM tiles) at qwen3-0.6b (G = 2) and qwen3-4b (G = 4)
    widths: one Mosaic kernel in the program."""
    b, n_pool = 8, 8 * n_t + 1
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    text = _compiled_text(
        paged_decode_attention_pallas,
        s((b, h, DH), jnp.bfloat16),
        s((n_pool, BS, KV, DH), jnp.bfloat16),
        s((n_pool, BS, KV, DH), jnp.bfloat16),
        s((b, n_t), jnp.int32),
        s((b,), jnp.int32),
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_latent_attention_kernels_compile_for_v5e(one_chip, kind):
    """Both attention kernels in Moonlight-16B-A3B's absorbed geometry (16
    query heads over one 576-lane latent head whose first 512 lanes are
    the values, scale 1/sqrt(192)) at the benchmark's 16 rows, 256 lanes
    and 160-block tables: the chunked-prefill kernel's 4096 x 576 query
    block must be cut into query tiles to fit its fast memory, and the
    decode kernel's block copies must cover the pool's 640 padded lanes."""
    from functools import partial

    b, n_t, w = 16, 160, 256
    n_pool = b * n_t + 1
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    pool = s((n_pool, BS, 1, 576), jnp.bfloat16)
    if kind == "prefill":
        fn = partial(mixed_prefill_attention_pallas, scale=192 ** -0.5, v_dim=512)
        text = _compiled_text(lambda q, k, t, d: fn(q, k, None, t, d), s((b, w, 16, 576), jnp.bfloat16),
                              pool, s((b, n_t), jnp.int32), s((b, 4), jnp.int32))
    else:
        fn = partial(paged_decode_attention_pallas, scale=192 ** -0.5, v_dim=512)
        text = _compiled_text(lambda q, k, t, n: fn(q, k, None, t, n), s((b, 16, 576), jnp.bfloat16),
                              pool, s((b, n_t), jnp.int32), s((b,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_retrieval_topk_bitonic_compiles_for_v5e(one_chip):
    q, n, d, k = 32, 16_384, 768, 32
    text = _compiled_text(
        lambda qs, cs: retrieval_topk_pallas(qs, cs, k, merge="bitonic"),
        jax.ShapeDtypeStruct((q, d), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=one_chip),
    )
    assert "tpu_custom_call" in text


def test_full_width_mixed_step_compiles_for_v5e(one_chip):
    """The served step at qwen3-0.6b's published widths (28 layers, d=1024,
    vocab 151,936) with the Pallas attention path: the kernel must be in
    the program and the program must fit one chip's 16 GB."""
    cfg = get_config("qwen3-0.6b").with_overrides(attn_impl="pallas")
    pol = ShardingPolicy(rules=base_rules(False), mesh=None)
    b, w, n_t = 8, 256, 33
    n_pool = b * n_t + 1
    params = _shapes(
        jax.eval_shape(lambda: init_params(LM.param_specs(cfg), jax.random.PRNGKey(0))),
        one_chip,
    )
    cache = _shapes(
        jax.eval_shape(lambda: LM.init_paged_cache(cfg, n_pool, BS, b, dtype=jnp.bfloat16)),
        one_chip,
    )
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731

    def step(params, tokens, cache, tables, q_start, q_len):
        return LM.mixed_step(cfg, pol, params, tokens, cache, tables, q_start, q_len, BS)

    compiled = (
        jax.jit(step)
        .lower(params, i32(b, w), cache, i32(b, n_t), i32(b), i32(b))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    )
    assert total < 16e9, f"{total / 1e9:.2f} GB does not fit one v5e"
