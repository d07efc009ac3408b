"""Bring-up smoke of C-FedRAG on a TPU: the federated RAG path end to end,
through its normal entry points, at the published widths of qwen3-0.6b
(28 layers, d=1024, 16/8 heads of 128, d_ff 3072, vocab 151,936, qk-norm,
tied embeddings), with bf16 activations and KV pool and random weights
made from ``--seed``.

    python chip_smoke.py               # one chip: the main path
    python chip_smoke.py --four-chips  # four chips: the sharded KV pool only

One chip.  ``CFedRAGSystem`` stands up four providers (attestation, sealed
channels, ``bag_embed`` index) whose top-k runs through the Pallas kernel,
checked against the jnp reference.  ``CFedRAGSystem.serve`` collects,
reranks, builds prompts and decodes through the paged ``ServeEngine``
(unified mixed dispatch, prefix cache, token budget, Pallas attention),
then serves the same queries again from the prefix cache.  One mixed step
and one paged decode step are cross-checked between the Pallas and the XLA
attention paths, and the compiled served steps must contain the kernels.

Four chips.  The same queries are served with the KV pool sharded over
four chips and over one; the answers must be token-identical.

Every failed check exits non-zero.  The script refuses to run where JAX
finds no TPU, and starts no other process.  Times it prints are readings
of one smoke run, not benchmark numbers.  The last line of stdout is the
JSON record of the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.pipeline import CFedRAGConfig, CFedRAGSystem  # noqa: E402
from repro.data.corpus import make_federated_corpus  # noqa: E402
from repro.data.tokenizer import HashTokenizer  # noqa: E402
from repro.kernels.retrieval_topk.ops import retrieval_topk  # noqa: E402
from repro.kernels.retrieval_topk.ref import retrieval_topk_ref  # noqa: E402
from repro.launch.serve import overlap_reranker  # noqa: E402
from repro.models import lm as LM  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.runtime.sharding import ShardingPolicy, base_rules  # noqa: E402
from repro.serving.engine import ServeConfig, ServeEngine, engine_generator  # noqa: E402
from repro.serving.kv_cache import blocks_for  # noqa: E402

N_FACTS = 128
N_QUERIES = 8
MAX_BATCH = 8
MAX_PROMPT = 512
MAX_NEW = 16
BLOCK = 16
TOKEN_BUDGET = 256
POL = ShardingPolicy(rules=base_rules(False), mesh=None)

# Retrieval scores are cosines of unit-norm f32 embeddings.  A matmul that
# rounds its operands to bf16 (8 significant bits) moves such a score by at
# most 2 * 2^-9 = 3.9e-3 (Cauchy-Schwarz), so kernel scores must sit within
# 1e-2 of the exact ones, and two ids may swap ranks only where their exact
# scores lie within 1e-2 of each other.
SCORE_TOL = 1e-2
# The Pallas and XLA attention paths read the same bf16 q/k/v but round at
# different points: XLA rounds the softmax probabilities to bf16 before
# P @ V, the kernel keeps them in f32.  That is a relative difference of
# about 2^-9 to 2^-8 in each layer's attention output, carried through a
# bf16 residual stream over 28 layers: about sqrt(28) * 2^-8 = 2% if the
# layers' differences are independent, 28 * 2^-8 = 11% if they all line
# up.  A lane is accepted within 0.1 relative L2 error of its f32 logits;
# a wrong mask, block or lane puts a lane's error near 1.
LOGIT_TOL = 0.1


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"ok   {what}")


def model_config():
    return get_config("qwen3-0.6b").with_overrides(attn_impl="pallas", dtype="bfloat16")


def make_params(cfg, seed: int):
    return init_params(LM.param_specs(cfg), jax.random.PRNGKey(seed))


def make_system(cfg, params, corpus, tok, shards=None):
    engine = ServeEngine(
        cfg, POL, params,
        ServeConfig(
            max_batch=MAX_BATCH, max_prompt_len=MAX_PROMPT, max_new_tokens=MAX_NEW,
            paged=True, prefix_cache=True, token_budget=TOKEN_BUDGET,
            block_size=BLOCK, shards=shards,
        ),
    )
    system = CFedRAGSystem(
        corpus,
        CFedRAGConfig(aggregation="rerank", split_by="corpus", use_pallas=True),
        tokenizer=tok,
        reranker=overlap_reranker(tok),
        generator=engine_generator(engine),
    )
    return system, engine


def check_results(results, n_providers: int, vocab: int, label: str) -> None:
    check(len(results) == N_QUERIES, f"{label}: one result per query")
    for i, r in enumerate(results):
        check(
            r["status"] == "done" and not r.get("truncated") and not r.get("degraded"),
            f"{label}: query {i} done, not truncated, not degraded",
        )
        check(r["n_providers"] == n_providers, f"{label}: query {i} heard all {n_providers} providers")
        ans = np.asarray(r["answer_tokens"])
        check(ans.size >= 1 and ((ans >= 0) & (ans < vocab)).all(),
              f"{label}: query {i} answer of {ans.size} tokens inside the vocabulary")


def check_retrieval(system, texts) -> None:
    """Each provider's Pallas top-k against the jnp reference on the same
    queries, judged by exact float64 scores."""
    q_tok = np.stack([system.tok.encode(t, max_len=24) for t in texts])
    for p in system.providers:
        q_emb = np.asarray(p.embed_fn(q_tok))
        m = min(system.cfg.m_local, len(p.chunks))
        s_k, i_k = (np.asarray(a) for a in retrieval_topk(q_emb, p.embeddings, m, use_pallas=True))
        with jax.default_matmul_precision("highest"):
            _, i_r = retrieval_topk_ref(jnp.asarray(q_emb), jnp.asarray(p.embeddings), m)
        i_r = np.asarray(i_r)
        exact = q_emb.astype(np.float64) @ p.embeddings.astype(np.float64).T
        rows = np.arange(len(texts))[:, None]
        score_err = float(np.abs(s_k - exact[rows, i_k]).max())
        gap = np.abs(exact[rows, i_k] - exact[rows, i_r])
        swaps = int((i_k != i_r).sum())
        print(f"     provider {p.provider_id}: {len(p.chunks)} chunks, top-{m}, "
              f"max score error {score_err:.3g}, {swaps} rank swaps")
        check(score_err <= SCORE_TOL, f"provider {p.provider_id}: Pallas scores within {SCORE_TOL}")
        check(bool(((i_k == i_r) | (gap <= SCORE_TOL)).all()),
              f"provider {p.provider_id}: Pallas ids match the reference outside near-ties")
        text = retrieval_topk.lower(q_emb, p.embeddings, k=m, use_pallas=True).compile().as_text()
        check("tpu_custom_call" in text, f"provider {p.provider_id}: top-k kernel compiled into its program")


def record_first_call(engine, name: str) -> dict:
    """Wrap the engine's jitted step ``name`` so its first call records the
    argument shapes; ``lower`` on them later yields the program served."""
    step = getattr(engine, name)
    seen: dict = {"step": step}

    def wrapper(*args):
        if "args" not in seen:
            seen["args"] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), args
            )
        return step(*args)

    setattr(engine, name, wrapper)
    return seen


@jax.jit
def compare_logits(lp, lx):
    """Per lane: relative L2 error of the f32 logits, argmax agreement, and
    whether both are finite."""
    err = jnp.linalg.norm(lp - lx, axis=-1) / jnp.maximum(jnp.linalg.norm(lx, axis=-1), 1e-30)
    finite = jnp.isfinite(lp).all(-1) & jnp.isfinite(lx).all(-1)
    return err, jnp.argmax(lp, -1) == jnp.argmax(lx, -1), finite


def check_attention_paths(cfg, params, seed: int) -> None:
    """Two mixed steps (a fill from empty, then a step that reads those
    blocks back beside fresh lanes) and one paged decode step, each run
    with ``attn_impl="pallas"`` and ``"flash_jnp"`` on identical inputs."""
    cfg_x = cfg.with_overrides(attn_impl="flash_jnp")
    b, w = MAX_BATCH, TOKEN_BUDGET
    n_t = blocks_for(MAX_PROMPT + MAX_NEW, BLOCK)
    tables = jnp.arange(b * n_t, dtype=jnp.int32).reshape(b, n_t)
    rng = np.random.default_rng(seed)
    hi = min(cfg.vocab_size, HashTokenizer().vocab_size)
    tok = lambda shape: jnp.asarray(rng.integers(8, hi, shape), jnp.int32)  # noqa: E731

    def mixed(c):
        return jax.jit(lambda p, kv, t, q0, ql: LM.mixed_step(
            c, POL, p, t, kv, tables, q0, ql, BLOCK))

    def decode(c):
        return jax.jit(lambda p, kv, t, q0, ql: LM.decode_step(
            c, POL, p, kv, t, q0, block_tables=tables, block_size=BLOCK))

    q_len_a = np.array([256, 200, 129, 64, 17, 16, 1, 0], np.int32)
    q_len_b = np.array([1, 1, 40, 64, 1, 100, 16, 30], np.int32)
    steps = [
        ("fill", mixed, tok((b, w)), np.zeros(b, np.int32), q_len_a),
        ("mixed", mixed, tok((b, w)), q_len_a, q_len_b),
        ("decode", decode, tok((b, 1)), q_len_a + q_len_b, np.ones(b, np.int32)),
    ]
    cache = LM.init_paged_cache(cfg, b * n_t + 1, BLOCK, b, dtype=jnp.bfloat16)
    for label, make, t, q0, ql in steps:
        q0, ql = jnp.asarray(q0), jnp.asarray(ql)
        lp, next_cache = make(cfg)(params, cache, t, q0, ql)
        lx, _ = make(cfg_x)(params, cache, t, q0, ql)
        live = np.arange(t.shape[1])[None, :] < np.asarray(ql)[:, None]
        err, agree, finite = (np.asarray(a)[live] for a in compare_logits(lp, lx))
        del lp, lx
        print(f"     {label} step: {live.sum()} live lanes, relative logit error "
              f"median {np.median(err):.3g} max {err.max():.3g}, argmax agrees on "
              f"{agree.sum()}/{agree.size} lanes")
        check(bool(finite.all()), f"{label} step: Pallas and XLA logits finite")
        check(float(err.max()) <= LOGIT_TOL, f"{label} step: Pallas and XLA logits within {LOGIT_TOL}")
        cache = next_cache


def run_one_chip(cfg, seed: int) -> None:
    compile_s = [0.0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t_start = time.perf_counter()
    params = make_params(cfg, seed)
    corpus = make_federated_corpus(
        n_facts=N_FACTS, n_distractors=N_FACTS, n_queries=N_QUERIES, seed=seed
    )
    tok = HashTokenizer()
    system, engine = make_system(cfg, params, corpus, tok)
    texts = [q.text for q in corpus.queries[:N_QUERIES]]
    n_prov = len(system.providers)
    print(f"     {n_prov} providers over {len(corpus.chunks)} chunks, {len(texts)} queries")

    check_retrieval(system, texts)

    mixed_seen = record_first_call(engine, "_mixed_rows")
    decode_seen = record_first_call(engine, "_decode_chunk")
    c0, t0 = compile_s[0], time.perf_counter()
    engine.serve_prompts([np.full((4,), 9, np.int32)], max_new_tokens=2)
    print(f"     warm-up serve: {time.perf_counter() - t0:.2f} s wall, "
          f"{compile_s[0] - c0:.2f} s of it XLA compile")

    t0 = time.perf_counter()
    first = system.serve(texts, max_new_tokens=MAX_NEW)
    st1 = dict(system.last_serve_stats)
    print(f"     cold serve (smoke time, not a benchmark): {time.perf_counter() - t0:.2f} s, "
          f"{st1['mixed_dispatches']} mixed + {st1['decode_dispatches']} decode dispatches "
          f"over {st1['engine_steps']} steps")
    check_results(first, n_prov, cfg.vocab_size, "cold serve")
    check(st1["mixed_dispatches"] >= 1, "cold serve ran the unified mixed dispatch")

    t0 = time.perf_counter()
    second = system.serve(texts, max_new_tokens=MAX_NEW)
    st2 = dict(system.last_serve_stats)
    same = sum(np.array_equal(a["answer_tokens"], b["answer_tokens"]) for a, b in zip(first, second))
    print(f"     warm serve (smoke time, not a benchmark): {time.perf_counter() - t0:.2f} s, "
          f"prefix hits {st2['prefix_hits']}/{st2['prefix_lookups']}, "
          f"{st2['prefill_tokens_saved']} prefill tokens saved, "
          f"{same}/{len(texts)} answers token-identical to the cold serve")
    check_results(second, n_prov, cfg.vocab_size, "warm serve")
    check(st2["prefix_hits"] >= 1, "warm serve hit the prefix cache")

    for name, seen in (("mixed", mixed_seen), ("decode", decode_seen)):
        check("args" in seen, f"served {name} step was dispatched")
        text = seen["step"].lower(*seen["args"]).compile().as_text()
        check("tpu_custom_call" in text, f"served {name} step has the Pallas kernel in its program")

    check_attention_paths(cfg, engine.params, seed)

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"     XLA compile total: {compile_s[0]:.2f} s; device peak bytes in use: "
          f"{peak if peak is not None else 'not reported'}; "
          f"smoke wall time (not a benchmark): {time.perf_counter() - t_start:.2f} s")


def run_four_chips(cfg, seed: int) -> None:
    n_dev = len(jax.devices())
    check(n_dev >= 4, f"four devices present (found {n_dev})")
    t_start = time.perf_counter()
    params = make_params(cfg, seed)
    corpus = make_federated_corpus(
        n_facts=N_FACTS, n_distractors=N_FACTS, n_queries=N_QUERIES, seed=seed
    )
    tok = HashTokenizer()
    texts = [q.text for q in corpus.queries[:N_QUERIES]]
    answers = {}
    for shards in (4, 1):
        system, engine = make_system(cfg, params, corpus, tok, shards=shards)
        t0 = time.perf_counter()
        res = system.serve(texts, max_new_tokens=MAX_NEW)
        print(f"     shards={shards} serve (smoke time, not a benchmark): "
              f"{time.perf_counter() - t0:.2f} s")
        check_results(res, len(system.providers), cfg.vocab_size, f"shards={shards}")
        answers[shards] = [np.asarray(r["answer_tokens"]) for r in res]
        if shards == 4:
            mesh_devices = set(engine._mesh.devices.flat)
            pool = NamedSharding(engine._mesh, PartitionSpec(None, "data"))
            for leaf in jax.tree.leaves(engine._cache):
                held = sorted((s.device.id, s.data.shape[1]) for s in leaf.addressable_shards)
                check(
                    leaf.sharding.is_equivalent_to(pool, leaf.ndim)
                    and [n for _, n in held] == [1, 1, 1, 1],
                    f"pool leaf {leaf.shape} split one shard per chip {[d for d, _ in held]}",
                )
            check(
                all(
                    l.sharding.is_fully_replicated and l.sharding.device_set == mesh_devices
                    for l in jax.tree.leaves(engine.params)
                ),
                "weights replicated on all four chips",
            )
        del system, engine
    same = [np.array_equal(a, b) for a, b in zip(answers[4], answers[1])]
    print(f"     shards=4 vs shards=1: {sum(same)}/{len(same)} answers token-identical")
    check(all(same), "shards=4 answers token-identical to shards=1")
    print(f"     smoke wall time (not a benchmark): {time.perf_counter() - t_start:.2f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-pool phase: shards=4 against shards=1")
    ap.add_argument("--seed", type=int, default=0, help="seed of weights, corpus and inputs")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's device is {dev.platform!r}); "
              "this smoke runs on the chip only", file=sys.stderr)
        return 1
    print(f"     cache: {use_compile_cache()}; device: {dev.device_kind} x {len(jax.devices())}")
    cfg = model_config()
    if args.four_chips:
        run_four_chips(cfg, args.seed)
    else:
        run_one_chip(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
