"""Record the small chip trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py [--out PATH]   # on the chip

A two-layer engine at qwen3-0.6b's widths serves two short prompts under
the profiler with the benchmark's options and spans (``bench.window``
around the serve, ``bench.round`` around a host pause), and the trace is
written to ``bench/tests/data/small.xplane.pb``.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse

    import jax
    import numpy as np

    from bench.lib import record, spec, trace
    from bench.lib import weights as W

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "bench", "tests", "data", "small.xplane.pb"))
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    from repro.runtime.sharding import ShardingPolicy, base_rules
    from repro.serving.engine import ServeConfig, ServeEngine

    with open(os.path.join(ROOT, "bench", "configs", "qwen3-0.6b-fed4.json")) as f:
        m = dict(json.load(f), num_hidden_layers=2)
    arch = spec.arch(m)
    cfg = arch.model_config(m)
    params = arch.program_params(W.make(arch, m, 7, "bfloat16"), m)
    s = m["serving"]
    eng = ServeEngine(cfg, ShardingPolicy(rules=base_rules(False), mesh=None), params, ServeConfig(
        max_batch=s["max_batch"], max_prompt_len=s["max_prompt_len"], max_new_tokens=s["max_new_tokens"],
        paged=True, prefix_cache=True, token_budget=s["token_budget"], block_size=s["block_size"]))
    prompts = [np.arange(8, 8 + 300, dtype=np.int32) + i for i in range(2)]
    eng.serve_prompts(prompts, max_new_tokens=4)  # compile
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=trace.options())
    with record.span("bench.window"):
        with record.span("bench.round"):
            time.sleep(0.02)
        eng.serve_prompts([p + 1000 for p in prompts], max_new_tokens=12)
    jax.profiler.stop_trace()
    out = args.out
    os.makedirs(os.path.dirname(out), exist_ok=True)
    shutil.copy(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0], out)
    shutil.rmtree(d, ignore_errors=True)
    print(f"wrote {out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
