"""Moonlight-16B-A3B (``deepseek_v3``) on the program's serving path,
against its plain reference (``bench/refs/deepseek_v3.py``), at the
architecture module's ``TINY`` size on the CPU, weights made from a seed
by the benchmark's own generator, both attention kernels interpreted.

Tolerances, each with its reason:

* ``LOGIT_TOL`` 2e-4 of the logits' standard deviation: the program runs
  in float32 here, as the reference does, and differs only by the order
  of the same products (the absorbed form folds W_uk into the query and
  W_uv after attention; the kernels' online softmax reassociates).  Those
  orders move a logit by 4.7e-6 of its spread here; the same program in
  bfloat16 moves it by 0.093, far outside the tolerance.
* the share test's 1e-5 (relative): the same sums in another order.
"""
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import spec
from bench.lib import weights as W
from bench.run import run_cell, serve_window
from bench.tests.tiny import plant, tiny_cell

CELL = "eval-cot-moonlight"
# the cell's own per-layer metrics; it also joins every metric that lists
# eval-cot-4b
METRICS = ("prefill_attn_roofline.mla", "expert_ffn_roofline")
# The tiny cell is judged by the Qwen3 cells' limits, not by its chip
# limits: over 24-28 served tokens at TINY widths the program read
# logit_gap 0-0.037 and the control 0.37-1.44 (seeds 11, 12 and SEED, CPU),
# so at this size the control fails by the logit gap too, not only by
# retrieval.
LIMITS = {"logit_gap": 0.25, "score_err": 0.0025, "rank_gap": 0.0025, "prompt_diff": 0,
          "providers_missing": 0}
SEED = 3141592653589
LOGIT_TOL = 2e-4
BS = 8


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the benchmark's ``BENCHMARK.json`` and files, whose
    ``bench/limits`` holds the tiny cell's limits."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    top = os.path.join(root, os.path.basename(spec.BENCH_DIR))
    os.makedirs(os.path.join(top, "limits"))
    with open(os.path.join(top, "limits", CELL + ".json"), "w") as f:
        json.dump(LIMITS, f)
    for sub in os.listdir(spec.BENCH_DIR):
        if sub != "limits" and os.path.isdir(os.path.join(spec.BENCH_DIR, sub)):
            os.symlink(os.path.join(spec.BENCH_DIR, sub), os.path.join(top, sub))
    return root


@pytest.fixture(scope="module")
def tiny(root):
    """(cell, configuration, weights, program config, program params), all
    in float32."""
    cell = tiny_cell(CELL, root=root)
    m = cell.model
    w = W.make(cell.arch, m, SEED, "float32")
    cfg = cell.arch.model_config(m).with_overrides(attn_impl="pallas", dtype="float32",
                                                   param_dtype="float32")
    return cell, m, w, cfg, cell.arch.program_params(w, m)


def _pol():
    from repro.runtime.sharding import ShardingPolicy, base_rules

    return ShardingPolicy(rules=base_rules(False), mesh=None)


def _steps(cfg):
    from repro.models import lm as LM

    pol = _pol()
    mixed = jax.jit(lambda p, t, c, tb, q0, ql: LM.mixed_step(cfg, pol, p, t, c, tb, q0, ql, BS))
    decode = jax.jit(lambda p, c, t, pos, tb: LM.decode_step(
        cfg, pol, p, c, t, pos, block_tables=tb, block_size=BS))
    return mixed, decode


def _rel(a, b, ref):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max() / np.asarray(ref).std())


def test_prefill_in_two_steps_then_decode_matches_the_reference_forward(tiny):
    """A 21-token prompt prefilled through the mixed step in chunks of 13
    and 8 (the second resumes mid-block), then 6 tokens decoded through
    the paged decode step, each position's logits against the reference's
    full forward of the whole sequence."""
    from repro.models import lm as LM

    cell, m, w, cfg, params = tiny
    toks = np.random.default_rng(1).integers(1, m["vocab_size"], 27).astype(np.int32)
    ref = np.asarray(cell.reference.forward_logits(m, w, toks))
    mixed, decode = _steps(cfg)
    n_t = 5
    cache = LM.init_paged_cache(cfg, n_t + 1, BS, 1, dtype=jnp.float32)
    tables = jnp.arange(n_t, dtype=jnp.int32)[None]
    got, start = [], 0
    for n in (13, 8):
        t = np.zeros((1, 16), np.int32)
        t[0, :n] = toks[start : start + n]
        lg, cache = mixed(params, jnp.asarray(t), cache, tables, jnp.asarray([start]), jnp.asarray([n]))
        got.append(np.asarray(lg[0, :n]))
        start += n
    for pos in range(start, len(toks)):
        lg, cache = decode(params, cache, jnp.asarray(toks[pos : pos + 1])[None],
                           jnp.asarray([pos]), tables)
        got.append(np.asarray(lg[0]))
    got = np.concatenate(got)
    assert got.shape == ref.shape
    assert _rel(got, ref, ref) < LOGIT_TOL


@pytest.mark.parametrize("live_max", [None, 16])
def test_a_row_is_the_same_alone_or_beside_seven_others(tiny, live_max):
    """Dropless: row 0's logits do not depend on what the other rows of its
    step carry, through the mixed step (all lanes, or the engine's
    gathering of at most ``live_max`` live lanes) and the decode step."""
    from repro.models import lm as LM
    from repro.runtime.sharding import ShardingPolicy, base_rules

    cell, m, w, cfg, params = tiny
    pol = ShardingPolicy(rules=base_rules(False), mesh=None)
    mixed = jax.jit(lambda p, t, c, tb, ql: LM.mixed_step(
        cfg, pol, p, t, c, tb, jnp.zeros(8, jnp.int32), ql, BS, live_max=live_max))
    _, decode = _steps(cfg)
    rng = np.random.default_rng(2)
    toks = rng.integers(1, m["vocab_size"], (8, 16)).astype(np.int32)
    n_t = 3
    tables = jnp.arange(8 * n_t, dtype=jnp.int32).reshape(8, n_t)
    out = []
    for q_len in ([6] + [0] * 7, [6, 3, 1, 2, 1, 1, 1, 1]):  # 16 live lanes at most
        cache = LM.init_paged_cache(cfg, 8 * n_t + 1, BS, 8, dtype=jnp.float32)
        lg, cache = mixed(params, jnp.asarray(toks), cache, tables, jnp.asarray(q_len, jnp.int32))
        dl, _ = decode(params, cache, jnp.asarray(toks[:, :1]), jnp.asarray(q_len, jnp.int32), tables)
        out.append((np.asarray(lg[0, :6]), np.asarray(dl[0])))
    np.testing.assert_array_equal(out[0][0], out[1][0])
    np.testing.assert_array_equal(out[0][1], out[1][1])


def test_the_eight_shares_add_up_to_the_whole_layer(tiny):
    """model-configs §4: the expert layer of each of 8 chips (its 8 experts
    of 64, the router at full width), with the shared experts counted
    once, sums to the uncut reference's whole layer."""
    from repro.models import moe as MOE

    cell, m, w, cfg, params = tiny
    rng = np.random.default_rng(3)
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    e_all = m["deployment"]["router_width"]
    lw = {k: np.asarray(v[0], np.float32) for k, v in w["moe"].items()}
    big = {k: rng.normal(0, 1 / np.sqrt(d if k != "wd" else f), (e_all,) + lw[k].shape[1:])
           .astype(np.float32) for k in ("wg", "wu", "wd")}
    x = rng.normal(0, 1, (2, 5, d)).astype(np.float32)
    whole_m = dict(m, deployment=dict(m["deployment"], experts_held=list(range(e_all))))
    ref_w = dict(lw, **big)
    with jax.default_matmul_precision("highest"):
        mm = lambda s, a, b: jnp.einsum(s, a, b, precision=jax.lax.Precision.HIGHEST)  # noqa: E731
        whole = np.asarray(cell.reference.moe(jnp.asarray(x), ref_w, whole_m, mm))
        shared = np.asarray(cell.reference._mlp(mm, jnp.asarray(x), lw["shared_wg"], lw["shared_wu"],
                                                lw["shared_wd"]))
        total = -(7 * shared)
        held = m["n_routed_experts"]
        p = {k: jnp.asarray(lw[k]) for k in ("router", "select_bias")}
        p["shared"] = {k: jnp.asarray(lw["shared_" + k]) for k in ("wg", "wu", "wd")}
        for chip in range(e_all // held):
            c = cfg.with_overrides(expert_offset=chip * held)
            p.update({k: jnp.asarray(big[k][chip * held : (chip + 1) * held]) for k in ("wg", "wu", "wd")})
            out, _ = MOE.held_moe_apply(c, _pol(), p, jnp.asarray(x))
            total = total + np.asarray(out)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5 * np.abs(whole).max())


def test_a_prefix_hit_on_a_block_boundary_copies_the_latent_block(tiny):
    """The second serve of a prompt of whole blocks hits the whole prefix,
    so copy-on-write copies its last block (in every latent leaf, the
    leading dense layer's too) before the last prompt token is rewritten:
    its answer equals the first's."""
    from repro.models import lm as LM
    from repro.serving.engine import ServeConfig, ServeEngine

    cell, m, w, cfg, params = tiny
    cache = LM.init_paged_cache(cfg, 6, BS, 1, dtype=jnp.float32)
    cache = jax.tree.map(lambda a: jnp.arange(a.size, dtype=a.dtype).reshape(a.shape), cache)
    copied = LM.paged_copy_block(cfg, cache, 2, 4)
    for leaf, before in zip(jax.tree.leaves(copied), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(leaf[:, 4], before[:, 2])
        np.testing.assert_array_equal(np.delete(leaf, 4, 1), np.delete(before, 4, 1))
    assert set(cache) == {"pos0", "lead"}

    eng = ServeEngine(cfg, _pol(), params, ServeConfig(
        max_batch=2, max_prompt_len=64, max_new_tokens=8, paged=True, prefix_cache=True,
        block_size=BS, token_budget=32))
    prompt = np.random.default_rng(4).integers(1, m["vocab_size"], 3 * BS).astype(np.int32)
    first = eng.serve_prompts([prompt], max_new_tokens=6)
    second = eng.serve_prompts([prompt], max_new_tokens=6)
    assert eng.prefix_hits >= 1
    np.testing.assert_array_equal(first[0], second[0])


def test_the_tiny_cell_is_correct_and_its_control_is_not(root):
    cell = tiny_cell(CELL, root=root)
    out, smp, chunks = serve_window(cell, SEED, 2.0, False, time.monotonic())
    from bench.lib import check

    program = check.verdict(check.compare(cell, chunks, smp, SEED), cell.limits)
    control = check.verdict(check.compare(cell, chunks, smp, SEED, control=True), cell.limits)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert program[0], program[1]
    assert not control[0], control[1]


def test_a_step_that_leaves_the_latent_pool_unchanged_is_not_correct(root):
    out = run_cell(tiny_cell(CELL, root=root), SEED, 2.0, False, time.monotonic(), alter=plant("stale_cache"))
    assert not out["correct"], out["checks"]


def test_the_cell_resolves_by_name_in_a_checkout_that_holds_it():
    """The cell of ``BENCHMARK.json``: its files are found by name, it
    reports ``setup_s``, ``answer_tok_s`` and its own per-layer metrics,
    and its limits name every number the check compares."""
    cell = spec.cell(CELL)
    assert cell.chips == 1
    names = {m["name"] for m in cell.end_to_end}
    assert names == {"answer_tok_s", "setup_s"}
    assert set(METRICS) < {m["name"] for m in cell.per_layer}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m).value)
    assert set(cell.limits) == set(LIMITS)
    assert callable(cell.reference.logit_gaps)


def test_the_configuration_resolves_to_its_architecture_module():
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == "moonlight-16b-a3b-fed4")
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        model = json.load(f)
    assert entry["reduced"] == model["reduced"] == ["n_routed_experts"]
    arch = spec.arch(model)
    assert arch.__file__ == os.path.join(spec.BENCH_DIR, "archs", "deepseek_v3.py")
    for k in ("global_leaves", "layer_groups", "model_config", "program_params", "step_flops",
              "attn_work"):
        assert callable(getattr(arch, k)), k
    assert set(arch.TINY) <= set(model)
    groups = arch.layer_groups(model)
    assert set(groups) == {"dense", "moe"} and all(n > 0 and leaves for n, leaves in groups.values())
    assert set(arch.global_leaves(model)).isdisjoint(groups)
    assert model["n_routed_experts"] == len(model["deployment"]["experts_held"]) == 8
