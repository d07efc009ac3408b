"""A cell of ``BENCHMARK.json`` cut to a size the CPU runs in seconds: the
model to its architecture module's ``TINY`` sizes, a corpus of 128 short
chunks, two slots.  Only the sizes change; the harness, the program's
path and the check are the ones a chip run uses."""
from __future__ import annotations

import copy

from bench.lib import spec

SERVING = dict(max_batch=2, token_budget=64, max_prompt_len=256, max_new_tokens=16)
CORPUS = dict(n_facts=64, n_distractors=64, chunk_words_median=12, chunk_max_len=32)


def tiny_cell(name: str, root: str = spec.ROOT) -> spec.Cell:
    """The cell ``name`` of the benchmark at ``root``, cut."""
    cell = spec.cell(name, root=root)
    cell.model = copy.deepcopy(cell.model)
    cell.model.update(cell.arch.TINY)
    cell.model["serving"].update(SERVING)
    cell.model["corpus"].update(CORPUS)
    cell.traffic = copy.deepcopy(cell.traffic)
    t = cell.traffic
    if t["kind"] == "open_poisson":
        t["rate_qps"] = 3.0
        t["drain_s"] = 30
    else:
        t["backlog"] = 16
        t["collect_batch"] = 2
    t["answer_tokens"] = {"dist": "uniform_int", "lo": 2, "hi": 6, "block": 5}
    t["check_tokens"] = 24
    return cell


def plant(fault: str):
    """A fault in the timed path, planted after set-up: ``token`` alters the
    answer tokens where the engine's steps produce them, ``stale_cache``
    has both step programs return the KV pool unchanged (a step that
    returns its state as it came), ``half_providers`` drops half of the
    providers' responses from every federated round (half of the batch
    left out), ``retrieval``
    makes every provider return a wrong chunk at rank 0, ``prompt`` drops
    one token of every prompt the orchestrator builds."""
    import numpy as np

    def token(dep):
        eng = dep.engine
        mixed, decode = eng._mixed_rows, eng._decode_chunk

        def mixed_rows(*a):
            res = mixed(*a)
            return res[:6] + (res[6].at[:, 1:].add(1),)

        def decode_chunk(*a):
            res = decode(*a)
            return res[:4] + (res[4].at[:, 1:].add(1),)

        eng._mixed_rows, eng._decode_chunk = mixed_rows, decode_chunk

    def stale_cache(dep):
        eng = dep.engine
        mixed, decode = eng._mixed_rows, eng._decode_chunk

        def mixed_rows(params, cache, *a):
            return (cache,) + mixed(params, cache, *a)[1:]

        def decode_chunk(params, cache, *a):
            return (cache,) + decode(params, cache, *a)[1:]

        eng._mixed_rows, eng._decode_chunk = mixed_rows, decode_chunk

    def half_providers(dep):
        orch = dep.system.orchestrator
        orig = orch.collect_contexts_batch

        def collect(texts, **kw):
            responses = orig(texts, **kw)
            return responses[: len(responses) // 2]

        orch.collect_contexts_batch = collect

    def retrieval(dep):
        for p in dep.system.providers:
            orig = p.retrieve

            def retrieve(q, m, orig=orig, p=p):
                out = dict(orig(q, m))
                ids = np.array(out["chunk_ids"])
                ids[..., 0] = p._chunk_id_arr[-1] if ids[..., 0].ravel()[0] != p._chunk_id_arr[-1] else p._chunk_id_arr[0]
                out["chunk_ids"] = ids
                return out

            p.retrieve = retrieve

    def prompt(dep):
        orch = dep.system.orchestrator
        orig = orch.build_prompt
        orch.build_prompt = lambda *a, **k: np.delete(orig(*a, **k), 3, axis=1)

    return {"token": token, "stale_cache": stale_cache, "half_providers": half_providers, "retrieval": retrieval, "prompt": prompt}[fault]


def run_tiny(name: str, seed: int, fault: str | None = None, control: bool = False) -> dict:
    import time

    from bench.run import run_cell

    cell = tiny_cell(name)
    seconds = 3.0 if cell.traffic["kind"] == "open_poisson" else 4.0
    return run_cell(cell, seed, seconds, False, time.monotonic(),
                    alter=plant(fault) if fault else None, control=control)
