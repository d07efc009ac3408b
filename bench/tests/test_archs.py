"""The Qwen3 architecture module gives what the harness gave before the
architecture seam: the same weight bits from a seed, and the same
operations and bytes for the same live geometry, through the module and
through the metric arithmetic (``derive.step_mfu``,
``derive.kernel_roofline``).  ``data/golden_qwen3.json`` was recorded on
the harness as it stood before the seam (``work.Shape``,
``weights.make`` with its fixed key list): SHA-256 of every leaf of two
tiny Qwen3 configurations (tied and untied head) at two seeds, and the
counts of a fixed geometry of mixed steps and decode chunks at three
sizes."""
import hashlib
import json
import os
import types

import jax
import numpy as np
import pytest

from bench.lib import derive, spec, work
from bench.lib import weights as W
from bench.lib.record import Run

with open(os.path.join(os.path.dirname(__file__), "data", "golden_qwen3.json")) as f:
    GOLDEN = json.load(f)
QWEN3 = spec.arch({"model_type": "qwen3"})
PEAK = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


def _config(name: str) -> dict:
    if name in GOLDEN["models"]:
        return GOLDEN["models"][name]
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("model", sorted(GOLDEN["models"]))
@pytest.mark.parametrize("seed", GOLDEN["seeds"])
def test_weights_are_the_bits_recorded_before_the_seam(model, seed):
    w = W.make(QWEN3, _config(model), seed, "bfloat16")
    got = {jax.tree_util.keystr(p): hashlib.sha256(np.asarray(a).tobytes()).hexdigest()
           + f" {a.dtype} {list(a.shape)}" for p, a in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == GOLDEN["weights"][f"{model}/{seed}"]


def _golden_live() -> work.Live:
    live = work.Live()
    for d in GOLDEN["geometry"]["mixed"]:
        work.mixed_live(live, **{k: np.asarray(v) for k, v in d.items()})
    for d in GOLDEN["geometry"]["decode"]:
        work.decode_live(live, **{k: np.asarray(v) for k, v in d.items()})
    return live


class _Trace:
    """Device times of the engine's programs and of a kernel, fixed."""

    def module_ns(self, pattern):
        return 3.0e9

    def kernel_ns(self, program):
        return 1.5e9


@pytest.mark.parametrize("name", sorted(GOLDEN["counts"]))
def test_live_counts_are_the_counts_recorded_before_the_seam(name):
    m, want = _config(name), GOLDEN["counts"][name]
    live = _golden_live()
    assert {k: getattr(live, k) for k in GOLDEN["live"]} == GOLDEN["live"]
    assert QWEN3.step_flops(m, live) == want["step_flops"]
    for which in ("prefill", "decode"):
        assert QWEN3.attn_work(m, live, which) == (want[which + "_flops"], want[which + "_bytes"])
    # the metric arithmetic reaches the same counts through the cell's architecture
    cell = types.SimpleNamespace(model=m, arch=QWEN3)
    run = Run(seconds=1.0, traced=True, trace=_Trace(), extra={"peak": PEAK})
    run.steps = types.SimpleNamespace(live=_golden_live)
    assert derive.step_mfu(run, cell) == 100.0 * want["step_flops"] / (3.0 * PEAK["bf16_flops"])
    for which in ("prefill", "decode"):
        share, _ = work.roofline_share(want[which + "_flops"], want[which + "_bytes"], 1.5, PEAK)
        assert derive.kernel_roofline(run, cell, "p", which) == 100.0 * share


def test_layer_groups_number_their_leaves_in_order():
    """Two groups of layers, as a leading dense layer before expert layers
    would be: the j-th layer leaf, counted through the groups in order,
    comes from ``fold_in(fold_in(seed key, 16 + j), layer in its group)``
    and each global leaf from ``fold_in(seed key, its place)`` (a wrong key
    draws other numbers altogether; within one, the jitted maker may round
    the scaling differently in the last place)."""
    glob = {"embed": ((8, 4), 0.02), "final_norm": ((4,), None)}
    groups = {"dense": (1, {"norm": ((4,), None), "w": ((4, 6), 0.5)}),
              "experts": (3, {"router": ((4, 2), 0.5), "w": ((2, 4, 3), 0.1)})}
    arch = types.SimpleNamespace(global_leaves=lambda m: glob, layer_groups=lambda m: groups)
    w = W.make(arch, {}, 2**31 + 11, "float32")
    key = W.seed_key(2**31 + 11)

    def draw(k, shape, std):
        x = jax.random.normal(k, shape, np.float32)
        return np.asarray(1.0 + 0.1 * x if std is None else std * x)

    for i, (name, (shape, std)) in enumerate(glob.items()):
        np.testing.assert_allclose(w[name], draw(jax.random.fold_in(key, i), shape, std), rtol=1e-6)
    j = 16
    for group, (n, leaves) in groups.items():
        for name, (shape, std) in leaves.items():
            assert w[group][name].shape == (n, *shape)
            for layer in range(n):
                k = jax.random.fold_in(jax.random.fold_in(key, j), layer)
                np.testing.assert_allclose(w[group][name][layer], draw(k, shape, std), rtol=1e-6)
            j += 1
