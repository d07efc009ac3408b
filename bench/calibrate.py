"""Readings that the correctness limits of a cell are set from, on the chip.

    python3 bench/calibrate.py --workload rag-mc-0.6b --seeds 11,12,13 --seconds 51

For each seed, in one process: the cell's set-up and window at its own
load, then the numbers compared, once for the program and once for the
control (the reference in float8 in the program's place, read at the same
prompts and served tokens).  One JSON line per seed.  A limit lies above
the largest program reading and below the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench.lib import check, spec
    from bench.run import serve_window, use_compile_cache

    cell = spec.cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out, smp, chunks = serve_window(cell, seed, args.seconds, False, time.monotonic())
        program = check.compare(cell, chunks, smp, seed)
        control = check.compare(cell, chunks, smp, seed, control=True)
        print(json.dumps({"seed": seed, "attempted": out["attempted"], "failed": out["failed"],
                          "metrics": out["metrics"], "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
