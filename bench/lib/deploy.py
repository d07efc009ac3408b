"""Stand up the system under test from a configuration file: the model on
the paged engine, four providers, the orchestrator.  Besides the
architecture modules (``bench/archs``), which bind a model to the
program's config and parameter tree, this is the only module of the
benchmark that imports the program.

The configuration file holds the published model config (Hugging Face
keys) at its top level and the deployment in nested groups: ``serving``
(engine settings), ``federation`` (providers and aggregation) and
``corpus`` (the generated data).
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import numpy as np

from bench.lib import corpus as C
from bench.lib import weights as W


@dataclasses.dataclass
class Deployment:
    system: object  # repro.core.pipeline.CFedRAGSystem
    engine: object  # repro.serving.engine.ServeEngine
    chunks: list
    questions: list


def build(cell, seed: int) -> Deployment:
    """The cell's deployment, its weights made from ``seed``."""
    from repro.core.pipeline import CFedRAGConfig, CFedRAGSystem
    from repro.data.corpus import Chunk, FederatedCorpus
    from repro.data.tokenizer import HashTokenizer
    from repro.launch.serve import overlap_reranker
    from repro.runtime.sharding import ShardingPolicy, base_rules
    from repro.serving.engine import ServeConfig, ServeEngine, engine_generator

    m, arch = cell.model, cell.arch
    cfg = arch.model_config(m)
    params = arch.program_params(W.make(arch, m, seed, m["torch_dtype"]), m)
    s, f = m["serving"], m["federation"]
    engine = ServeEngine(
        cfg, ShardingPolicy(rules=base_rules(False), mesh=None), params,
        ServeConfig(
            max_batch=s["max_batch"], max_prompt_len=s["max_prompt_len"],
            max_new_tokens=s["max_new_tokens"], paged=True, prefix_cache=s["prefix_cache"],
            token_budget=s["token_budget"], block_size=s["block_size"],
        ),
    )
    chunks, questions = C.make_corpus(m["corpus"])
    program_chunks = [Chunk(c.text, c.corpus, c.site, c.chunk_id, c.fact_id) for c in chunks]
    tok = HashTokenizer()
    system = CFedRAGSystem(
        FederatedCorpus(chunks=program_chunks, queries=[]),
        CFedRAGConfig(
            m_local=f["m_local"], n_global=f["n_global"], aggregation=f["aggregation"],
            split_by=f["split_by"], embed_dim=f["embed_dim"],
            chunk_max_len=m["corpus"]["chunk_max_len"], use_pallas=f["use_pallas"],
        ),
        tokenizer=tok, reranker=overlap_reranker(tok), generator=engine_generator(engine),
    )
    return Deployment(system, engine, chunks, questions)


def warm_engine(dep: Deployment) -> None:
    """Compile (or load) the engine's step programs at the served shapes:
    prompts that fill more than one token budget (the mixed step), a few
    tokens of decode (the fused decode chunk), and the same prompts again,
    whose full-prefix hit runs the copy-on-write block copy.  The prompts
    are made of an id no real prompt starts with, so they seed no prefix
    hit for the traffic."""
    scfg = dep.engine.scfg
    prompts = [np.full((scfg.token_budget + scfg.block_size,), 8191 - i, np.int32) for i in range(2)]
    for _ in range(2):
        dep.engine.serve_prompts(prompts, max_new_tokens=4)
    jax.block_until_ready(dep.engine._cache)


def warm_collect(dep: Deployment, texts: list[str]) -> None:
    """One federated round at the cell's round batch: compiles the
    embedder and the four providers' top-k programs at that batch."""
    orch = dep.system.orchestrator
    responses = orch.collect_contexts_batch(texts)
    contexts = orch.aggregate_batch(texts, responses)
    for q, c in zip(texts, contexts):
        orch.build_prompt(q, c, max_len=dep.engine.scfg.max_prompt_len)


def free(dep: Deployment) -> None:
    """Drop every device buffer the program holds."""
    dep.engine.reset_cache()
    dep.engine.params = None
    for p in dep.system.providers:
        p.embeddings = None
    dep.system = dep.engine = None
    gc.collect()


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
