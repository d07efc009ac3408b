"""BENCHMARK.json: every cell finds its configuration, architecture module,
reference, traffic, generator kind, metric readers and limits by name, and
the file keeps the shape the benchmark's contract asks for."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench.lib import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_by_name(name):
    cell = spec.cell(name, BENCH)
    assert cell.chips in (1, 4)
    for k in ("plan", "warm", "drive"):
        assert callable(getattr(cell.kind, k))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m).value)
    for k in ("logit_gap", "score_err", "rank_gap", "prompt_diff", "providers_missing"):
        assert k in cell.limits
    ref = os.path.join(spec.BENCH_DIR, "refs", cell.model["reference"] + ".py")
    assert os.path.exists(ref) and callable(cell.reference.logit_gaps)


ARCH_API = ("global_leaves", "layer_groups", "model_config", "program_params", "step_flops", "attn_work")


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_resolves_to_an_architecture_module(name):
    conf = {c["name"]: c for c in BENCH["configs"]}[name]
    with open(os.path.join(spec.ROOT, conf["file"])) as f:
        model = json.load(f)
    arch = spec.arch(model)
    assert arch.__file__ == os.path.join(spec.BENCH_DIR, "archs", model["model_type"] + ".py")
    for k in ARCH_API:
        assert callable(getattr(arch, k)), k
    assert set(arch.TINY) <= set(model)
    groups = arch.layer_groups(model)
    assert groups and all(n > 0 and leaves for n, leaves in groups.values())
    assert set(arch.global_leaves(model)).isdisjoint(groups)


def test_model_type_without_a_module_fails_naming_the_missing_file(tmp_path):
    """No fallback: a configuration whose ``model_type`` has no module under
    ``archs/`` stops the cell, and the message names the file."""
    with open(os.path.join(spec.ROOT, BENCH["configs"][0]["file"])) as f:
        model = dict(json.load(f), model_type="no_such_arch")
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "c.json").write_text(json.dumps(model))
    bench = dict(BENCH, configs=[dict(BENCH["configs"][0], name="c", file="bench/configs/c.json")],
                 workloads=[dict(BENCH["workloads"][0], config="c")])
    missing = os.path.join(str(tmp_path), "bench", "archs", "no_such_arch.py")
    with pytest.raises(FileNotFoundError, match=re.escape(missing)):
        spec.cell(bench["workloads"][0]["name"], bench, root=str(tmp_path))
    with pytest.raises(FileNotFoundError, match=re.escape(os.path.join(spec.BENCH_DIR, "archs", "no_such_arch.py"))):
        spec.arch(model)


def test_benchmark_file_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and 1 <= BENCH["run_seconds"] <= 51
    confs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(spec.ROOT, c["file"]))
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            model = json.load(f)
        assert model["source"] == c["source"] and model["reduced"] == c["reduced"]
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and NAME.match(w["name"])
        assert w["config"] in confs and len(w["why"]) <= 200
        used.add(w["config"])
    assert used == set(confs)
    seen = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload", BENCH["workloads"][0]["name"],
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
