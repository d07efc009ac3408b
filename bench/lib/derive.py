"""Arithmetic shared by several metric readers."""
from __future__ import annotations

from bench.lib import stats
from bench.lib import work
from bench.lib.trace import covered, union

# the engine's jitted step programs, as their executions are named in the
# device trace
STEP_PROGRAMS = r"jit_(mixed_rows|decode_chunk)\("
ENGINE_PROGRAMS = r"jit_(mixed_rows|decode_chunk|cow_copy|upload_block)\("


def latencies_ms(run) -> tuple[list[float], int]:
    """Due-to-answer times of the window's queries that were served, and
    how many were not (failed, or still unanswered when the run gave up)."""
    window = run.in_window()
    done = [(q.answered - q.due) * 1e3 for q in window if q.status == "done"]
    return done, len(window) - len(done)


def query_percentile(run, q: float) -> float:
    """A percentile over every query due in the window; one that was not
    served counts as slower than all that were, at the longest of their
    latencies and of the times the run waited for the unserved (a finite
    stand-in for missing, which JSON can carry)."""
    done, missing = latencies_ms(run)
    waited = [(run.t_end - x.due) * 1e3 for x in run.in_window() if x.status != "done"]
    return stats.percentile(done, q, n_missing=missing, missing_value=max(done + waited, default=0.0))


def step_mfu(run, cell) -> float | None:
    """Useful FLOPs of the window's dispatches over the device time of the
    engine's programs at the chip's bf16 peak, in percent."""
    if run.trace is None or run.steps is None:
        return None
    t = run.trace.module_ns(ENGINE_PROGRAMS) / 1e9
    if t <= 0:
        return None
    flops = cell.arch.step_flops(cell.model, run.steps.live(), run)
    return 100.0 * flops / (t * run.extra["peak"]["bf16_flops"])


def kernel_roofline(run, cell, program: str, which: str) -> float | None:
    """Least time for the live attention work of one kernel over the
    kernel's own device time, in percent.  ``program``: the engine program
    whose Pallas kernel it is; ``which``: "prefill" for the chunked-prefill
    kernel of the mixed step, "decode" for the paged decode kernel of the
    fused decode chunk."""
    if run.trace is None or run.steps is None:
        return None
    t = run.trace.kernel_ns(program) / 1e9
    if t <= 0:
        return None
    flops, nbytes = cell.arch.attn_work(cell.model, run.steps.live(), which, run)
    share, bound = work.roofline_share(flops, nbytes, t, run.extra["peak"])
    run.extra.setdefault("bounds", {})[which] = bound
    return 100.0 * share


def idle_share(run, pending_only: bool) -> float | None:
    """Share of time with work pending in which no device operation ran, in
    percent.  ``pending_only``: count only the spans in which some query
    is inside the engine (submitted, not yet answered)."""
    red = run.trace
    if red is None:
        return None
    if not pending_only:
        total = red.window[1] - red.window[0]
        return 100.0 * (1.0 - red.busy_ns() / total)
    spans = union(
        (red.to_trace(q.submitted), red.to_trace(q.answered))
        for q in run.queries if q.submitted is not None and q.answered is not None
    )
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    return 100.0 * (1.0 - covered(union(red.ops), spans) / total)
