"""The program's own spans (``repro.runtime.tracing``), as the metric
readers take them: the log of the traced window (the program records
while the profiler runs), the spans that start in the window, the spans
under a span, the union of intervals inside one, and the monotonic clock
put on the device trace's.  On a program without the tracing module the
log is None and every reader returns None."""
from __future__ import annotations

from bench.lib.trace import covered, union


def log() -> list | None:
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    return tracing.spans()


def starting_in_window(recs, run, name: str) -> list:
    lo, hi = run.t0 * 1e9, run.t1 * 1e9
    return [r for r in recs if r.name == name and lo <= r.t0 < hi]


def under(recs, root) -> list:
    """Every span below ``root``, at any depth."""
    kids: dict[int, list] = {}
    for r in recs:
        kids.setdefault(r.parent, []).append(r)
    out, todo = [], [root.id]
    while todo:
        for r in kids.get(todo.pop(), []):
            out.append(r)
            todo.append(r.id)
    return out


def cover_ns(recs, within) -> float:
    """Time inside ``within`` that the union of ``recs`` covers."""
    return covered(union((r.t0, r.t1) for r in recs), [(within.t0, within.t1)])


def on_trace_clock(run, recs) -> list[tuple[float, float]]:
    """The union of the spans' intervals on the device trace's clock (ns)."""
    return union((run.trace.to_trace(r.t0 / 1e9), run.trace.to_trace(r.t1 / 1e9)) for r in recs)


def served_requests(recs, run, name: str) -> list:
    """The ``req.prefill`` or ``req.decode`` span of each query of the
    window that was served (the request's tag is the query's index)."""
    window = {id(q) for q in run.in_window() if q.status == "done"}
    out = []
    for r in recs:
        tag = r.attrs.get("tag") if r.name == name else None
        if isinstance(tag, int) and 0 <= tag < len(run.queries) and id(run.queries[tag]) in window:
            out.append(r)
    return out


def dispatch_idle_ms(run) -> float | None:
    """Device-idle time of the traced window inside the engine's
    ``engine.dispatch`` spans, summed, in ms."""
    recs = log()
    if recs is None or run.trace is None:
        return None
    disp = [r for r in recs if r.name == "engine.dispatch"]
    if not disp:
        return None
    return covered(on_trace_clock(run, disp), run.trace.idle_gaps()) / 1e6
