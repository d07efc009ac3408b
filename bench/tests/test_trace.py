"""The trace reduction, on a small trace recorded on a TPU v5e
(``record_trace.py``: a two-layer engine at qwen3-0.6b's widths serving
two short prompts)."""
import os

import numpy as np
import pytest

from bench.lib import trace as tr

PATH = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return tr.reduce(PATH)


def test_device_and_spans_are_found(red):
    assert red.n_devices == 1
    names = {n for n, _, _ in red.spans}
    assert {"bench.window", "bench.round"} <= names
    lo, hi = red.window
    assert hi > lo and np.isfinite(red.offset_ns)
    # the spans carry the monotonic clock: the window's start maps back
    # onto itself within a millisecond
    start_mono = (lo - red.offset_ns) / 1e9
    assert abs(red.to_trace(start_mono) - lo) < 1e6


def test_busy_and_idle_partition_the_window(red):
    lo, hi = red.window
    busy = red.busy_ns()
    idle = sum(e - s for s, e in red.idle_gaps())
    assert 0 < busy < hi - lo
    assert abs(busy + idle - (hi - lo)) < 1e-6 * (hi - lo) + 1
    # no loop container is left among the operations: they would count a
    # loop body's idle bubbles as busy
    assert not any(tr.CONTAINER.match(n) for n in red.op_names)


def test_kernels_are_found_by_the_program_that_runs_them(red):
    mixed = red.module_ns(r"jit_mixed_rows\(")
    decode = red.module_ns(r"jit_decode_chunk\(")
    k_mixed = red.kernel_ns(r"jit_mixed_rows\(")
    k_decode = red.kernel_ns(r"jit_decode_chunk\(")
    assert mixed > 0 and decode > 0
    assert 0 < k_mixed < mixed and 0 < k_decode < decode
    assert len(red.programs(r"jit_(mixed_rows|decode_chunk)\(")) >= 2
    # every operation of an engine step lies inside its program's execution
    inside = red.op_module >= 0
    assert inside.mean() > 0.9


def test_breakdown_lists_are_short_and_named(red):
    ops = tr.top_ops(red)
    assert 0 < len(ops) <= 10
    assert all(isinstance(n, str) and t > 0 for n, t in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])
    assert any(n.endswith(":pallas kernel") for n, _ in ops)
    gaps = tr.named_gaps(red, lambda s, e: "host")
    assert 0 < len(gaps) <= 10 and all(n == "host" and t > 0 for n, t in gaps)
    assert [t for _, t in gaps] == sorted((t for _, t in gaps), reverse=True)


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0.0, 3.0), (5.0, 8.0)]
    assert tr.covered(u, [(2, 6)]) == 2.0
