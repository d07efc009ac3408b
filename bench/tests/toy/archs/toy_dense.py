"""A toy architecture that exists only for the tests (``model_type:
toy_dense``): the Qwen3 dense decoder without qk-norm, which the program
runs with ``qk_norm=False``.  It reuses what it shares with Qwen3 from
``bench/archs/qwen3.py``; its reference is ``refs/toy_dense.py`` beside
it."""
from __future__ import annotations

import dataclasses
import os

from bench.lib import spec

_QWEN3 = spec.load_module(os.path.join(spec.BENCH_DIR, "archs", "qwen3.py"), "bench_arch_qwen3")

TINY = _QWEN3.TINY
global_leaves = _QWEN3.global_leaves
step_flops = _QWEN3.step_flops
attn_work = _QWEN3.attn_work


def layer_groups(m: dict) -> dict:
    n, leaves = _QWEN3.layer_groups(m)["layers"]
    return {"layers": (n, {k: v for k, v in leaves.items() if k not in ("q_norm", "k_norm")})}


def model_config(m: dict):
    return dataclasses.replace(_QWEN3.model_config(m), qk_norm=False)


def program_params(w: dict, m: dict) -> dict:
    l = w["layers"]
    p = {"embed": {"tok": w["embed"]}, "final_norm": w["final_norm"], "blocks": {"pos0": {
        "mixer_norm": l["attn_norm"],
        "attn": {k: l[k] for k in ("wq", "wk", "wv", "wo")},
        "ffn_norm": l["ffn_norm"],
        "mlp": {k: l[k] for k in ("wg", "wu", "wd")},
    }}}
    if not m["tie_word_embeddings"]:
        p["head"] = {"w": w["head"]}
    return p
