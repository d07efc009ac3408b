"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
and a traffic file; the traffic file names its generator kind
(``bench/kinds/<kind>.py``); each metric has a reader
(``bench/metrics/<name>.py``); the correctness limits of the cell are in
``bench/limits/<name>.json``.

Set-up (weights made on the device from the seed, the corpus, the
providers' indexes, warm-up of every served shape) is timed from process
start to the first timed query.  Then the window runs for ``--seconds``;
with ``--trace 1`` under the profiler, reporting the per-layer metrics in
place of the end-to-end ones.  Then the program's state is freed and the
sample of what the window served is compared with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``, each compared number beside its limit.
The last lines of standard error repeat the checks.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else at a fixed path in the checkout.
    Every program is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


COMPILES: list[tuple[float, float, str]] = []  # (monotonic end, seconds, event) of compile work

# JAX's events for tracing a function, lowering it, compiling it and
# loading it from the persistent cache: a shape first met in the window
# costs the window one or more of them, even when its program is cached
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache load",
}


def count_compiles() -> None:
    """Record the compile work of this process: every trace, lowering,
    backend compile and load from the persistent cache (JAX times a load
    as a backend compile too, so a real compile is one without a load)."""
    import jax

    if not getattr(count_compiles, "on", False):
        def on_event(event: str, duration: float, **_) -> None:
            if event in COMPILE_EVENTS:
                COMPILES.append((time.monotonic(), duration, COMPILE_EVENTS[event]))

        jax.monitoring.register_event_duration_secs_listener(on_event)
        count_compiles.on = True


def compile_summary(events) -> str:
    kinds = {}
    for _, d, k in events:
        n, t = kinds.get(k, (0, 0.0))
        kinds[k] = (n + 1, t + d)
    return ", ".join(f"{n} {k} ({t:.2f} s)" for k, (n, t) in kinds.items()) or "none"


def gap_namer(run, red):
    """Name a device idle gap by what the host was doing in it."""
    rounds = [(s, e) for n, s, e in red.spans if n == "bench.round"]
    pending = [
        (red.to_trace(q.submitted), red.to_trace(q.answered))
        for q in run.queries if q.submitted is not None and q.answered is not None
    ]

    def name(s, e):
        from bench.lib.trace import covered, union

        if covered(union(rounds), [(s, e)]) > 0.5 * (e - s):
            return "federated round on the host"
        if pending and covered(union(pending), [(s, e)]) < 0.5 * (e - s):
            return "no query in the engine"
        return "engine host loop"

    return name


def serve_window(cell, seed: int, seconds: float, traced: bool, t_start: float, alter=None):
    """Set up and run the window.  Returns the result object without its
    verdict, the sample of what the window served, and the corpus; the
    program's state is freed.  ``alter(deployment)``, when given, runs
    after set-up (the tests plant faults in the timed path with it)."""
    import jax
    import numpy as np

    from bench.lib import check, deploy, peaks, record
    from bench.lib import trace as tr

    count_compiles()
    dev = jax.devices()[0]
    dep = deploy.build(cell, seed)
    queries = cell.kind.plan(cell.traffic, dep.questions, seconds)
    cell.kind.warm(dep, cell.traffic, dep.questions)
    if alter is not None:
        alter(dep)
    run = record.Run(seconds=seconds, traced=traced)
    run.steps = record.StepRecorder(dep.engine)
    tdir = None
    if traced:
        run.extra["peak"] = peaks.peaks(dev.device_kind)
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
    eng = dep.engine
    counters = ("mixed_dispatches", "decode_dispatches", "prefix_hits", "prefix_lookups", "prefill_tokens_saved")
    c0 = [getattr(eng, k) for k in counters]
    run.setup_s = time.monotonic() - t_start
    with record.profiled(run, tdir):
        cell.kind.drive(dep, cell.traffic, queries, run)
    run.counters = {k: getattr(eng, k) - v for k, v in zip(counters, c0)}
    run.counters["prompt_tokens"] = sum(len(q.prompt) for q in queries if q.prompt is not None)
    peak_bytes = deploy.peak_bytes()
    run.steps.close()
    if traced:
        run.trace = tr.reduce(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)[0])
        shutil.rmtree(tdir, ignore_errors=True)

    inside = [c for c in COMPILES if run.t0 <= c[0] <= run.t_end]
    log(f"compile work in set-up: {compile_summary([c for c in COMPILES if c[0] < run.t0])}; "
        f"in the window and drain: {compile_summary(inside)}")
    COMPILES.clear()
    window = run.in_window()
    failed = [q for q in window if q.status != "done"]
    late = np.asarray(run.late) * 1e3 if run.late else np.zeros(1)
    log(f"window: {len(window)} queries, {len(failed)} failed, {run.counters}; "
        f"generator late (ms) p50 {np.percentile(late, 50):.3f} p99 {np.percentile(late, 99):.3f} "
        f"max {late.max():.3f}")

    longest = sorted(((e - t) * 1e3, t - run.t0, k) for k, t, e, _ in run.steps.calls)[::-1][:3]
    log("longest dispatches (ms, s after the window opened, kind): "
        + ", ".join(f"{d:.1f} {t:.2f} {k}" for d, t, k in longest))
    spans = [(k, e - run.t0, g * 1e3) for e, g, k in run.steps.host_gaps()]
    rounds = sorted(((e - s) * 1e3, s - run.t0, n) for s, e, n in run.rounds)[::-1][:3]
    log(f"dispatches: {len(run.steps.calls)}; longest host gaps (kinds, s after the window opened, ms): "
        + ", ".join(f"{k} {t:.2f} {g:.1f}" for k, t, g in spans)
        + "; longest rounds (ms, s after the window opened, batch): "
        + ", ".join(f"{d:.1f} {t:.2f} {n}" for d, t, n in rounds))
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = cell.reader(m).value(run, cell)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak_bytes}
    out = {"correct": False, "attempted": len(window), "failed": len(failed),
           "metrics": metrics, "device": device}
    if traced:
        red = run.trace
        device["busy_s"] = red.busy_ns() / 1e9
        device["window_s"] = (red.window[1] - red.window[0]) / 1e9
        out["breakdown"] = {"device_ops": tr.top_ops(red), "idle_gaps": tr.named_gaps(red, gap_namer(run, red))}
        if run.extra.get("bounds"):
            log(f"roofline bounds: {run.extra['bounds']}")

    smp = check.sample(window, cell.traffic["check_tokens"], seed)
    chunks = dep.chunks
    deploy.free(dep)
    return out, smp, chunks


def run_cell(cell, seed: int, seconds: float, traced: bool, t_start: float, alter=None,
             control: bool = False) -> dict:
    """Set up, run the window, check; returns the result object.
    ``control`` judges the control, the reference in a lower precision, in
    the program's place (its tests see ``correct`` come out false)."""
    from bench.lib import check

    out, smp, chunks = serve_window(cell, seed, seconds, traced, t_start, alter)
    numbers = check.compare(cell, chunks, smp, seed, control=control)
    ok, shown = check.verdict(numbers, cell.limits)
    log(f"check: {numbers['queries']} queries, {numbers['tokens']} served tokens compared")
    out["correct"] = bool(ok and not out["failed"])
    out["checks"] = shown
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import spec

    cell = spec.cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    log(f"compile cache: {use_compile_cache()}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
