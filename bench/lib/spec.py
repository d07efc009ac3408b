"""Find everything a cell needs by the names in ``BENCHMARK.json``: its
configuration file, its traffic file and that file's generator kind, its
metric readers and its correctness limits.  A cell, a traffic mix or a
metric is added by adding files; nothing here names one."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict  # the configuration file
    traffic: dict  # the traffic file, with its "kind"
    kind: object  # the generator module of that kind
    end_to_end: list[dict]  # metric entries reported with --trace 0
    per_layer: list[dict]  # metric entries reported with --trace 1
    limits: dict  # {number: limit} of the correctness comparison

    def reader(self, metric: dict):
        return load_module(os.path.join(BENCH_DIR, "metrics", metric["name"] + ".py"),
                           "bench_metric_" + metric["name"].replace(".", "_"))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark()
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        model = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    kind = load_module(os.path.join(BENCH_DIR, "kinds", traffic["kind"] + ".py"),
                       "bench_kind_" + traffic["kind"])
    with open(os.path.join(BENCH_DIR, "limits", name + ".json")) as f:
        limits = json.load(f)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported else [])]
    return Cell(name, w["chips"], model, traffic, kind, e2e, layer, limits)
