"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

A device plane (``/device:TPU:<n>``) carries a line of program executions
(``XLA Modules``: one event per run of a jitted program, named
``jit_<function>(<hash>)``) and a line of device operations (``XLA Ops``,
each named by its HLO instruction; a ``while`` loop's event spans the ops
of its body, which appear as events of their own).  Busy time is the union
of the operation intervals.  A Pallas kernel is a ``tpu_custom_call``
operation; its own name is not in the trace, so a kernel is found by the
program that runs it.  The benchmark's own host spans (``bench.*``) carry
the monotonic clock at their start, which puts the run's records on the
trace's clock.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
KERNEL = 'custom_call_target="tpu_custom_call"'
CONTAINER = re.compile(r"^%(while|conditional|call)[._]")


@dataclasses.dataclass
class Reduction:
    ops: np.ndarray  # (n, 2) start, end ns of device operations, sorted
    op_names: list[str]
    op_module: np.ndarray  # (n,) index of the program execution holding each op, -1 if none
    modules: np.ndarray  # (m, 2) start, end ns of program executions, sorted
    module_names: list[str]
    spans: list[tuple[str, float, float]]  # benchmark host spans: name, start, end ns
    offset_ns: float  # trace clock minus monotonic clock (ns)
    window: tuple[float, float]  # the traced window (the bench.window span), ns
    n_devices: int

    def to_trace(self, mono_s: float) -> float:
        return mono_s * 1e9 + self.offset_ns

    def busy_ns(self, lo: float | None = None, hi: float | None = None) -> float:
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        return covered(union(self.ops), [(lo, hi)])

    def module_ns(self, pattern: str) -> float:
        """Device time of the program executions whose name matches."""
        rx = re.compile(pattern)
        sel = [i for i, n in enumerate(self.module_names) if rx.search(n)]
        return float((self.modules[sel, 1] - self.modules[sel, 0]).sum()) if sel else 0.0

    def kernel_ns(self, program: str) -> float:
        """Device time of the Pallas kernels run by the programs whose
        name matches ``program``."""
        rx = re.compile(program)
        sel = [i for i, n in enumerate(self.op_names)
               if KERNEL in n and self.op_module[i] >= 0 and rx.search(self.module_names[self.op_module[i]])]
        return float((self.ops[sel, 1] - self.ops[sel, 0]).sum()) if sel else 0.0

    def programs(self, pattern: str) -> np.ndarray:
        """(k, 2) executions of the programs whose name matches, in order."""
        rx = re.compile(pattern)
        return self.modules[[i for i, n in enumerate(self.module_names) if rx.search(n)]].reshape(-1, 2)

    def idle_gaps(self) -> list[tuple[float, float]]:
        """Intervals of the window in which no operation ran."""
        busy = union(self.ops)
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, self.window[1])))
            t = max(t, e)
            if t >= self.window[1]:
                break
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return [(s, e) for s, e in gaps if e > s]


def union(iv) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def reduce(path: str) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, op_names, mods, mod_names, spans, offsets = [], [], [], [], [], []
    n_dev = 0
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        if CONTAINER.match(e.name):
                            continue  # a loop or branch: its body's ops are events of their own
                        ops.append((e.start_ns, e.start_ns + e.duration_ns))
                        op_names.append(e.name)
                elif line.name == "XLA Modules":
                    for e in line.events:
                        mods.append((e.start_ns, e.start_ns + e.duration_ns))
                        mod_names.append(e.name)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                        mono = _stat(e, "mono_ns")
                        if mono is not None:
                            offsets.append(e.start_ns - float(mono))
    if not n_dev:
        raise ValueError(f"no device plane in {path}")
    o_order, m_order = np.argsort([s for s, _ in ops]), np.argsort([s for s, _ in mods])
    window = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not window:
        raise ValueError(f"no bench.window span in {path}")
    ops_a = np.asarray(ops, np.float64).reshape(-1, 2)[o_order]
    mods_a = np.asarray(mods, np.float64).reshape(-1, 2)[m_order]
    # programs run one at a time: an op belongs to the last execution that
    # started before it, if that execution also ends after it
    i = np.searchsorted(mods_a[:, 0], ops_a[:, 0], side="right") - 1
    inside = (i >= 0) & (mods_a[np.maximum(i, 0), 1] >= ops_a[:, 1])
    return Reduction(
        ops=ops_a,
        op_names=[op_names[k] for k in o_order],
        op_module=np.where(inside, i, -1),
        modules=mods_a,
        module_names=[mod_names[k] for k in m_order],
        spans=sorted(spans, key=lambda x: x[1]),
        offset_ns=float(np.median(offsets)),
        window=window[0],
        n_devices=n_dev,
    )


def op_label(name: str) -> str:
    """An operation's kind: "pallas kernel", or its HLO name without the
    instance number (``fusion``, ``copy_bitcast_fusion``, ...)."""
    if KERNEL in name:
        return "pallas kernel"
    return re.sub(r"[._]\d+$", "", name.split(" = ")[0].lstrip("%"))


def program_label(name: str) -> str:
    return re.sub(r"^jit_|[(].*$", "", name)


def top_ops(red: Reduction, k: int = 10) -> list[list]:
    """The device operations that took most time, as ``[program:kind,
    seconds]``."""
    tot: dict[str, float] = {}
    for (s, e), n, m in zip(red.ops, red.op_names, red.op_module):
        key = f"{program_label(red.module_names[m]) if m >= 0 else '?'}:{op_label(n)}"
        tot[key] = tot.get(key, 0.0) + (e - s)
    return [[n, t / 1e9] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def options():
    """Profiler options: device and benchmark spans, no Python tracer."""
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def named_gaps(red: Reduction, name_of, k: int = 10) -> list[list]:
    """The longest idle gaps, each named by ``name_of(start, end)`` (what
    the host was doing), as ``[name, seconds]``."""
    gaps = sorted(red.idle_gaps(), key=lambda g: g[0] - g[1])[:k]
    return [[name_of(s, e), (e - s) / 1e9] for s, e in gaps]
