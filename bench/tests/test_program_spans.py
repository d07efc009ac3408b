"""The metrics read from the program's own spans, on a tiny run of each
cell with the program recording (``tracing.recording()`` in place of the
profiler, which on the CPU has no device plane to reduce): the live work
summed from the ``engine.dispatch`` attrs is the work ``StepRecorder``
counts, every span reader of the cell returns a value, and a program
without the tracing module reads as nothing."""
import dataclasses
import sys
import time

import numpy as np
import pytest

from bench.lib import deploy, record, spans, work
from bench.lib.trace import Reduction
from bench.tests.tiny import tiny_cell
from repro.runtime import tracing

SPAN_METRICS = {
    "rag-mc-0.6b": ["round_device_wait_ms", "prefill_p90_ms", "decode_token_ms"],
    "eval-cot-4b": ["step_host_ms"],
}


def tiny_traced_run(name: str, seed: int):
    """Set up, warm and drive a tiny cell as ``bench/run.py`` does, with the
    program recording over the drive."""
    cell = tiny_cell(name)
    seconds = 3.0 if cell.traffic["kind"] == "open_poisson" else 4.0
    dep = deploy.build(cell, seed)
    queries = cell.kind.plan(cell.traffic, dep.questions, seconds)
    cell.kind.warm(dep, cell.traffic, dep.questions)
    run = record.Run(seconds=seconds, traced=True)
    run.steps = record.StepRecorder(dep.engine)
    tracing.clear()
    with tracing.recording():
        cell.kind.drive(dep, cell.traffic, queries, run)
    run.steps.close()
    recs = tracing.spans()
    tracing.clear()
    deploy.free(dep)
    return cell, run, recs


@pytest.fixture(scope="module", params=sorted(SPAN_METRICS))
def traced(request):
    return tiny_traced_run(request.param, 3141592653)


def dispatch_live(recs) -> work.Live:
    """The live work of the engine's dispatches, from their span attrs."""
    total = work.Live()
    for r in recs:
        if r.name != "engine.dispatch":
            continue
        a, live = r.attrs, work.Live()
        if a["kind"] == "mixed":
            work.mixed_live(live, a["q_start"], a["q_len"], a["is_decode"], a["done"],
                            a["lengths"], a["emitted"], a["row_len"])
        else:
            work.decode_live(live, a["lengths"], a["emitted"], a["emitted_after"], a["done"])
        total.add(live)
    return total


def test_dispatch_attrs_count_the_work_the_step_recorder_counts(traced):
    cell, run, recs = traced
    kinds = [r.attrs["kind"] for r in recs if r.name == "engine.dispatch"]
    assert kinds.count("mixed") == sum(k == "mixed" for k, *_ in run.steps.calls) > 0
    assert kinds.count("decode") == sum(k == "decode" for k, *_ in run.steps.calls)
    got, want = dispatch_live(recs), run.steps.live()
    assert want.tokens > 0
    assert dataclasses.asdict(got) == dataclasses.asdict(want)


def test_span_readers_read_the_run(traced, monkeypatch):
    cell, run, recs = traced
    monkeypatch.setattr(spans, "log", lambda: recs)
    listed = [m for m in cell.per_layer if m["source"] == "program_span"]
    names = {m["name"] for m in listed}
    assert set(SPAN_METRICS[cell.name]) <= names
    values = {}
    for m in listed:
        v = cell.reader(m).value(run, cell)
        if m["name"].startswith("dispatch_idle_ms"):
            assert v is None  # no device trace to read on the CPU
        else:
            assert v is not None and np.isfinite(v) and v >= 0, m["name"]
            values[m["name"]] = v
    if "round_device_wait_ms" in values:
        round_ms = cell.reader({"name": "round_ms"}).value(run, cell)
        assert 0 < values["round_device_wait_ms"] <= round_ms
    if "decode_token_ms" in values:
        assert values["decode_token_ms"] > 0 and values["prefill_p90_ms"] > 0


def test_dispatch_idle_is_the_idle_time_inside_dispatch_spans(monkeypatch):
    """Synthetic trace: device ops at [0, 10) and [30, 50) ms of a window
    [0, 60) ms whose clock runs 1 s ahead of the monotonic one; dispatches
    at [5, 35) and [45, 58) ms cover 20 ms of the gap (10, 30) and 8 ms
    of the tail (50, 60)."""
    ms = 1e6
    red = Reduction(ops=np.array([[0, 10], [30, 50]]) * ms, op_names=["a", "b"],
                    op_module=np.array([-1, -1]), modules=np.zeros((0, 2)), module_names=[],
                    spans=[], offset_ns=1e9, window=(0.0, 60 * ms), n_devices=1)
    run = record.Run(seconds=0.06, traced=True, t0=-1.0, t1=-0.94)
    run.trace = red
    tracing.clear()
    with tracing.recording():
        for lo, hi in ((5, 35), (45, 58)):
            tracing.record("engine.dispatch", int(-1e9 + lo * ms), int(-1e9 + hi * ms))
    try:
        assert spans.dispatch_idle_ms(run) == pytest.approx(20 + 8)
    finally:
        tracing.clear()
    run.trace = None
    assert spans.dispatch_idle_ms(run) is None


def test_readers_return_nothing_without_the_tracing_module(monkeypatch):
    """The parent of this change has no ``repro.runtime.tracing``: the
    readers must read nothing there, and not raise."""
    cell = tiny_cell("rag-mc-0.6b")
    run = record.Run(seconds=1.0, traced=True, t0=time.monotonic(), t1=time.monotonic() + 1)
    import repro.runtime

    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    monkeypatch.delattr(repro.runtime, "tracing")
    assert spans.log() is None
    for name in list(SPAN_METRICS["rag-mc-0.6b"]) + ["step_host_ms", "dispatch_idle_ms.rate",
                                                     "dispatch_idle_ms.eval"]:
        assert cell.reader({"name": name}).value(run, cell) is None, name
