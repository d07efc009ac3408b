"""A new architecture joins the benchmark by adding files alone.  The toy
architecture of ``toy/`` (the Qwen3 decoder without qk-norm) brings its
architecture module, its reference, a configuration, a traffic file,
limits and a ``BENCHMARK.json`` naming its cell; set beside the
benchmark's own generator kinds and metric readers in a scratch checkout,
its tiny cell runs through ``run.run_cell`` on the CPU and is judged
against its own reference: a sound run is correct, the control and a step
that returns the KV pool unchanged are not, and nothing under ``bench/``
is written."""
import os
import shutil
import time

import pytest

from bench.lib import spec
from bench.run import run_cell
from bench.tests.tiny import plant, tiny_cell

TOY = os.path.join(os.path.dirname(__file__), "toy")
CELL = "toy-rag-dense"
SEED = 2147483659


def _checkout(root: str) -> str:
    """The toy's files, and links to every generator kind and metric reader
    of the benchmark, laid out as a checkout."""
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(TOY, "BENCHMARK.json"), root)
    for sub in ("archs", "refs", "configs", "traffic", "limits"):
        shutil.copytree(os.path.join(TOY, sub), os.path.join(bench, sub))
    for sub in ("kinds", "metrics"):
        os.makedirs(os.path.join(bench, sub))
        for name in os.listdir(os.path.join(spec.BENCH_DIR, sub)):
            if name.endswith(".py"):
                os.symlink(os.path.join(spec.BENCH_DIR, sub, name), os.path.join(bench, sub, name))
    return root


def _files(top: str) -> dict:
    """Every file under ``top`` but byte-code caches, with its size and
    modification time."""
    out = {}
    for d, dirs, names in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            st = os.stat(os.path.join(d, n))
            out[os.path.join(d, n)] = (st.st_size, st.st_mtime_ns)
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _checkout(str(tmp_path_factory.mktemp("checkout")))


@pytest.mark.parametrize("case", ["sound", "control", "stale_cache"])
def test_an_architecture_added_by_files_runs_its_cell_against_its_own_reference(root, case):
    before = _files(spec.BENCH_DIR)
    cell = tiny_cell(CELL, root=root)
    assert cell.arch.__file__ == os.path.join(root, "bench", "archs", "toy_dense.py")
    assert cell.arch.model_config(cell.model).qk_norm is False
    out = run_cell(cell, SEED, 2.0, False, time.monotonic(), control=case == "control",
                   alter=plant(case) if case == "stale_cache" else None)
    if case == "sound":
        assert out["correct"], out["checks"]
        assert out["attempted"] > 0 and out["failed"] == 0
        assert set(out["metrics"]) == {"query_p50_ms", "setup_s"}
    else:
        assert not out["correct"], (case, out["checks"])
    assert _files(spec.BENCH_DIR) == before
