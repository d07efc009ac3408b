"""Rate sweep of an open-loop cell on the chip: the knee, once.

    python3 bench/sweep.py --workload rag-mc-0.6b --rates 0.3,0.5,0.7 --windows 2 --seconds 40

One process stands the cell's deployment up once, then runs ``--windows``
windows per rate, each with questions no earlier window asked.  For each rate it
prints the queries due, the backlog when the window closed (due but not
yet answered), the drain time after it, and the median and 90th
percentile of due-to-answer latency.  The knee is the highest rate whose
backlog does not grow through the window; the cell's traffic file takes
four fifths of it.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated arrivals per second")
    ap.add_argument("--windows", type=int, default=2, help="windows per rate")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench.lib import deploy, record, spec, stats
    from bench.run import use_compile_cache

    cell = spec.cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    dep = deploy.build(cell, args.seed)
    cell.kind.warm(dep, cell.traffic, dep.questions)
    print(f"set-up {time.monotonic() - T_START:.1f} s", flush=True)
    rates = [float(r) for r in args.rates.split(",")] * args.windows
    asked = dep.questions[: -cell.kind.ROUND_BATCHES[-1]]  # the warm-up asks the last ones
    per = len(asked) // len(rates)
    for w, rate in enumerate(rates):
        traffic = dict(cell.traffic, rate_qps=rate)
        queries = cell.kind.plan(traffic, asked[w * per : (w + 1) * per], args.seconds)
        run = record.Run(seconds=args.seconds, traced=False)
        cell.kind.drive(dep, traffic, queries, run)
        window = run.in_window()
        lat = [(q.answered - q.due) * 1e3 for q in window if q.status == "done"]
        backlog = sum(1 for q in window if q.answered is None or q.answered > run.t1)
        row = {
            "rate_qps": rate, "due": len(window), "served": len(lat), "backlog_at_close": backlog,
            "drain_s": run.t_end - run.t1,
            "p50_ms": stats.percentile(lat, 50) if lat else None,
            "p90_ms": stats.percentile(lat, 90) if lat else None,
            "late_max_ms": max(run.late) * 1e3 if run.late else None,
            "prompt_tokens_mean": sum(len(q.prompt) for q in window if q.prompt is not None) / max(1, len(window)),
            "answer_tokens": sum(len(q.answer) for q in window if q.answer is not None),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
