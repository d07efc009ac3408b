"""What a run records: each query of the window, each federated round,
and in a traced run the live geometry of each engine dispatch."""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class Query:
    index: int  # position in the traffic's question list
    text: str
    budget: int  # answer tokens asked for
    due: float | None = None  # scheduled arrival (open loop), monotonic seconds
    submitted: float | None = None  # the program's Request timestamps
    started: float | None = None
    finished: float | None = None
    answered: float | None = None  # when the answer reached the client
    status: str = "pending"  # pending | done | failed
    answer: np.ndarray | None = None
    prompt: np.ndarray | None = None  # the prompt the engine received
    responses: list | None = None  # per provider: {provider, scores, chunk_ids}


@dataclasses.dataclass
class Run:
    seconds: float
    traced: bool
    setup_s: float = 0.0
    t0: float = 0.0  # window, monotonic seconds
    t1: float = 0.0
    t_end: float = 0.0  # end of drain or of reading
    queries: list[Query] = dataclasses.field(default_factory=list)
    rounds: list[tuple[float, float, int]] = dataclasses.field(default_factory=list)  # start, end, batch
    late: list[float] = dataclasses.field(default_factory=list)  # open loop: round start - due
    steps: "StepRecorder | None" = None
    trace: object = None  # bench.lib.trace.Reduction of a traced run
    counters: dict = dataclasses.field(default_factory=dict)
    extra: dict = dataclasses.field(default_factory=dict)

    def in_window(self) -> list[Query]:
        """Open loop: the queries due in the window.  Closed loop: those
        answered in it."""
        if any(q.due is not None for q in self.queries):
            return [q for q in self.queries if q.due is not None and self.t0 <= q.due < self.t1]
        return [q for q in self.queries if q.answered is not None and q.answered <= self.t1]


def span(name: str):
    """A host span in the profiler's trace, carrying the monotonic clock so
    the trace can be put on the same time base as the run's records."""
    return jax.profiler.TraceAnnotation(name, mono_ns=time.monotonic_ns())


def response_rows(responses: list[dict], b: int) -> list[dict]:
    """Row ``b`` of each provider's batched response, without the chunk
    tokens (the check reads chunks from its own copy of the corpus)."""
    return [
        {"provider": int(r["provider"]), "scores": np.asarray(r["scores"])[b].copy(),
         "chunk_ids": np.asarray(r["chunk_ids"])[b].copy()}
        for r in responses
    ]


class StepRecorder:
    """Wraps the engine's two step programs and keeps, per dispatch, the
    host times of its call and of its end, and the arrays that say which
    lanes were live.  The end is read by waiting on the dispatch's
    ``emitted`` output, which the engine reads back right after the call
    anyway, so it adds no device sync; the arrays are read back only after
    the window.  ``close`` checks that every dispatch the engine counted
    went through a wrapped program: work on a program it does not wrap
    would drop out of every metric built on the recorder."""

    def __init__(self, engine):
        self.engine = engine
        self.calls: list[tuple[str, float, float, tuple]] = []  # kind, call, end, arrays
        self._orig = (engine._mixed_rows, engine._decode_chunk)
        self._counted = (engine.mixed_dispatches, engine.decode_dispatches)
        mixed, decode = self._orig

        def mixed_rows(params, cache, cur, lengths, emitted, done, budget, out,
                       tok, q_start, q_len, is_decode, row_len, b_new, tables):
            t = time.monotonic()
            res = mixed(params, cache, cur, lengths, emitted, done, budget, out,
                        tok, q_start, q_len, is_decode, row_len, b_new, tables)
            jax.block_until_ready(res[3])
            self.calls.append(("mixed", t, time.monotonic(),
                               (q_start, q_len, is_decode, done, lengths, emitted, row_len)))
            return res

        def decode_chunk(params, cache, cur, lengths, emitted, done, budget, out, n_steps, tables=None):
            t = time.monotonic()
            res = decode(params, cache, cur, lengths, emitted, done, budget, out, n_steps, tables)
            jax.block_until_ready(res[2])
            self.calls.append(("decode", t, time.monotonic(), (lengths, emitted, res[2], done)))
            return res

        engine._mixed_rows, engine._decode_chunk = mixed_rows, decode_chunk

    def close(self) -> None:
        eng = self.engine
        eng._mixed_rows, eng._decode_chunk = self._orig
        counted = (eng.mixed_dispatches - self._counted[0], eng.decode_dispatches - self._counted[1])
        seen = (sum(k == "mixed" for k, *_ in self.calls), sum(k == "decode" for k, *_ in self.calls))
        if counted != seen:
            raise RuntimeError(
                f"the engine counted {counted[0]} mixed and {counted[1]} decode dispatches, the "
                f"recorder saw {seen[0]} and {seen[1]}: a step program it does not wrap ran")

    def dispatches(self):
        """Each dispatch's call and end (monotonic seconds) and live work
        (a ``bench.lib.work.Live``)."""
        from bench.lib import work

        for kind, t, end, arrays in self.calls:
            live = work.Live()
            arrays = [np.asarray(a) for a in arrays]
            (work.mixed_live if kind == "mixed" else work.decode_live)(live, *arrays)
            yield t, end, live

    def live(self):
        """Live work of every recorded dispatch."""
        from bench.lib import work

        total = work.Live()
        for _, _, live in self.dispatches():
            total.add(live)
        return total

    def host_gaps(self, n: int = 5) -> list[tuple[float, float, str]]:
        """The ``n`` longest host turn-arounds between a dispatch's end and
        the next call: (end, seconds, kinds), longest first."""
        gaps = [(a[2], b[1] - a[2], f"{a[0]}>{b[0]}") for a, b in zip(self.calls, self.calls[1:])]
        return sorted(gaps, key=lambda g: -g[1])[:n]

    def answer_tokens(self, t0: float, t1: float) -> float:
        """Answer tokens emitted between ``t0`` and ``t1``: each dispatch's
        in the share of its span (call to end) that lies inside, so a
        dispatch cut by an edge counts for the part of it inside."""
        n = 0.0
        for t, end, live in self.dispatches():
            inside = min(end, t1) - max(t, t0)
            if inside > 0:
                n += live.head_tokens * inside / max(end - t, 1e-9)
        return n


@contextlib.contextmanager
def profiled(run: Run, directory: str | None):
    """Trace the device and the host while the body runs, when
    ``directory`` is given."""
    if directory is None:
        yield
        return
    from bench.lib.trace import options

    jax.profiler.start_trace(directory, profiler_options=options())
    try:
        with span("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()
