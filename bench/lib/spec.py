"""Find everything a cell needs by the names in ``BENCHMARK.json``: its
configuration file, the architecture module and the plain reference that
file names, its traffic file and that file's generator kind, its metric
readers and its correctness limits.  A cell, a traffic mix, a metric or
an architecture is added by adding files; nothing here names one.

An architecture is ``archs/<model_type>.py``, by the published
``model_type`` key of the configuration file.  It supplies what the
harness knows of a model:

* ``TINY``: the top-level keys of the configuration that the CPU tests
  cut (``bench/tests/tiny.py``);
* ``global_leaves(m)`` and ``layer_groups(m)``: the weights' leaf tables
  (``bench/lib/weights.py`` makes them from the seed);
* ``model_config(m)`` and ``program_params(weights, m)``: the program's
  model config and parameter tree (the module imports the program);
* ``step_flops(m, live, run)`` and ``attn_work(m, live, which, run)``:
  the live work behind ``step_mfu`` and the attention rooflines
  (``bench/lib/derive.py``), from the live geometry that
  ``bench/lib/work.py`` sums and, where the work depends on it, what the
  run recorded.

The reference is ``refs/<reference>.py``: ``logit_gaps(m, weights,
prompts, answers, quantize)`` (``bench/lib/check.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # where dataclasses look up the module's names
    spec.loader.exec_module(mod)
    return mod


def arch(model: dict, bench_dir: str = BENCH_DIR):
    """The architecture module of a configuration, by its ``model_type``."""
    path = os.path.join(bench_dir, "archs", model["model_type"] + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {model.get('name')!r} has model_type {model['model_type']!r}, "
            f"and there is no architecture module {path}")
    return load_module(path, "bench_arch_" + model["model_type"])


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict  # the configuration file
    arch: object  # the architecture module of its model_type
    reference: object  # the plain reference it names
    traffic: dict  # the traffic file, with its "kind"
    kind: object  # the generator module of that kind
    end_to_end: list[dict]  # metric entries reported with --trace 0
    per_layer: list[dict]  # metric entries reported with --trace 1
    limits: dict  # {number: limit} of the correctness comparison
    bench_dir: str = BENCH_DIR

    def reader(self, metric: dict):
        return load_module(os.path.join(self.bench_dir, "metrics", metric["name"] + ".py"),
                           "bench_metric_" + metric["name"].replace(".", "_"))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, bench: dict | None = None, root: str = ROOT) -> Cell:
    """The cell ``name`` of the benchmark whose ``BENCHMARK.json`` is at
    ``root``, its own files under ``root/bench``."""
    bench = bench or load_benchmark(root)
    bench_dir = os.path.join(root, os.path.basename(BENCH_DIR))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        model = json.load(f)
    arch_mod = arch(model, bench_dir)
    ref = load_module(os.path.join(bench_dir, "refs", model["reference"] + ".py"),
                      "bench_ref_" + model["reference"])
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    kind = load_module(os.path.join(bench_dir, "kinds", traffic["kind"] + ".py"),
                       "bench_kind_" + traffic["kind"])
    with open(os.path.join(bench_dir, "limits", name + ".json")) as f:
        limits = json.load(f)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported else [])]
    return Cell(name, w["chips"], model, arch_mod, ref, traffic, kind, e2e, layer, limits, bench_dir)
