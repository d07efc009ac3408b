"""Compile rehearsal: the served programs of each configuration, compiled
for a described TPU v5e without the chip, with each program's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [config ...] [--layers N]

For each configuration file under ``bench/configs`` (or those named), it
compiles the weight generator, the engine's unified mixed step and its
fused decode chunk at the served shapes, and prints what
``memory_analysis`` reports: arguments, outputs, temporaries, and their
sum against one chip's HBM.  ``--layers`` overrides the depth, to find
the deepest that fits.  Nothing runs, so it says nothing of time.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rehearse(m: dict, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    from bench.lib import spec
    from bench.lib import weights as W
    from repro.runtime.sharding import ShardingPolicy, base_rules
    from repro.serving.engine import ServeConfig, ServeEngine

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    arch = spec.arch(m)
    cfg = arch.model_config(m)
    s = m["serving"]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    tabs = W.tables(arch, m)
    gen = jax.jit(lambda k: W._make.__wrapped__(tabs, jax.random.wrap_key_data(k), m["torch_dtype"]))
    out = {"weights": gen.lower(key).compile()}
    w = placed(jax.eval_shape(gen, key))
    params = arch.program_params(w, m)
    eng = ServeEngine(cfg, ShardingPolicy(rules=base_rules(False), mesh=None), params, ServeConfig(
        max_batch=s["max_batch"], max_prompt_len=s["max_prompt_len"], max_new_tokens=s["max_new_tokens"],
        paged=True, prefix_cache=s["prefix_cache"], token_budget=s["token_budget"], block_size=s["block_size"],
    ))
    cache = placed(jax.eval_shape(eng._init_serve_cache))
    b, w_lanes, t_cap = s["max_batch"], s["token_budget"], s["max_new_tokens"]
    n_t = eng._blocks_per_slot

    def a(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    row = (a((b,)), a((b,)), a((b,)), a((b,), jnp.bool_), a((b,)), a((b, t_cap + 1)))
    out["mixed_rows"] = eng._mixed_rows.lower(
        params, cache, *row, a((b, w_lanes)), a((b,)), a((b,)), a((b,), jnp.bool_), a((b,)), a((b,)),
        a((b, n_t))).compile()
    out["decode_chunk"] = eng._decode_chunk.lower(params, cache, *row, a(()), a((b, n_t))).compile()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--layers", type=int)
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    files = sorted(glob.glob(os.path.join(ROOT, "bench", "configs", "*.json")))
    for path in files:
        with open(path) as f:
            m = json.load(f)
        if args.configs and m["name"] not in args.configs:
            continue
        if args.layers:
            m["num_hidden_layers"] = args.layers
        for prog, c in rehearse(m, one_chip).items():
            mem = c.memory_analysis()
            total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes \
                - mem.alias_size_in_bytes
            kernel = "tpu_custom_call" in c.as_text()
            print(f"{m['name']} layers={m['num_hidden_layers']} {prog}: args {mem.argument_size_in_bytes / 1e9:.3f} GB, "
                  f"out {mem.output_size_in_bytes / 1e9:.3f} GB, temp {mem.temp_size_in_bytes / 1e9:.3f} GB, "
                  f"alias {mem.alias_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB; "
                  f"Pallas kernel in program: {kernel}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
