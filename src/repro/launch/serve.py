"""C-FedRAG serving launcher: build the federated corpus, stand up the
providers + enclave orchestrator, and answer queries.

  python -m repro.launch.serve --queries 5 --aggregation rerank
  python -m repro.launch.serve --queries 5 --generate --deadline-s 0.5
  python -m repro.launch.serve --queries 16 --stream --collect-batch 4
  python -m repro.launch.serve --queries 16 --generate --paged --block-size 32
  python -m repro.launch.serve --queries 16 --token-budget 32 --prefix-cache
  python -m repro.launch.serve --queries 16 --prefix-cache --repeat 3
  python -m repro.launch.serve --queries 16 --generate --tenants 'interactive=4:1,batch=1'
  python -m repro.launch.serve --queries 16 --draft-k 3 --token-budget 32
  python -m repro.launch.serve --queries 16 --shards 4 --block-size 8

Uses the bag embedder + lexical-overlap reranker by default (training-free
CPU path).  ``--generate`` stands up a reduced-LM ``ServeEngine`` and
routes the whole query set through ``CFedRAGSystem.serve`` — concurrent
provider fan-out, continuous-batching generation, per-request p50/p95
latency (see examples/federated_medqa.py for the trained-LM loop)."""
from __future__ import annotations

import argparse

import numpy as np

from repro.core.pipeline import CFedRAGConfig, CFedRAGSystem
from repro.core.resilience import FaultSpec
from repro.data.corpus import make_federated_corpus
from repro.data.tokenizer import HashTokenizer
from repro.runtime.compile_cache import use_compile_cache


def overlap_reranker(tok: HashTokenizer):
    """Lexical-overlap cross-scorer (training-free F_aggr stand-in; the
    trained cross-encoder variant lives in benchmarks/table1).

    Accepts (query (S,), candidates (C, S)) -> (C,) scores, or a whole
    batch (queries (B, S), candidates (B, C, S)) -> (B, C) — the batched
    form the orchestrator's ``aggregate_batch`` uses (``supports_batch``)."""

    def _score_row(q: set, row: np.ndarray) -> float:
        c = set(int(t) for t in row if t > 7)
        return len(q & c) / (len(q) ** 0.5 * max(len(c), 1) ** 0.5)

    def rerank(query_tokens: np.ndarray, cand_tokens: np.ndarray) -> np.ndarray:
        cand_tokens = np.asarray(cand_tokens)
        if cand_tokens.ndim == 3:  # (B, C, S) batch
            return np.stack(
                [rerank(qt, ct) for qt, ct in zip(np.asarray(query_tokens), cand_tokens)]
            )
        q = set(int(t) for t in query_tokens if t > 7)
        return np.asarray([_score_row(q, row) for row in cand_tokens], np.float32)

    rerank.supports_batch = True
    return rerank


def make_demo_engine(max_new_tokens: int = 16, paged: bool = False,
                     block_size: int = 32, pool_blocks: int | None = None,
                     max_batch: int = 4, prefix_cache: bool = False,
                     token_budget: int | None = None,
                     spill_bytes: int | None = None, draft_k: int = 0,
                     shards: int | None = None):
    """Reduced-LM ServeEngine (random-init, CPU-sized) + generator adapter
    for the scheduler-driven serving demo.  ``paged=True`` swaps the
    per-slot cache stripes for the shared block pool (``--block-size``
    tokens per block; ``--pool-blocks`` caps the HBM budget, default =
    ``max_batch`` contiguous stripes) and runs the unified chunked-prefill
    loop — ONE mixed dispatch per engine step (``token_budget`` caps its
    prefill lanes, default whole-prompt); ``prefix_cache=True`` adds the
    RESIDENT refcounted prefix index on top, so repeated context preambles
    prefill once and share blocks across serve calls; ``spill_bytes``
    bounds an optional host-RAM demotion tier under it; ``draft_k > 0``
    turns on draft-k/verify-1 speculative decoding (self-speculation —
    the demo drafter IS the target, the accept-rate ceiling; a real
    deployment passes a small ``draft_config``/``draft_params`` pair);
    ``shards`` partitions the block pool over that many mesh devices and
    runs every engine step as ONE distributed mixed dispatch —
    bit-identical to the single-shard engine (tests/test_sharded_serving)."""
    import jax

    from repro.configs import get_config, smoke_config
    from repro.models import lm as LM
    from repro.models.params import init_params
    from repro.runtime.sharding import ShardingPolicy, base_rules
    from repro.serving.engine import ServeConfig, ServeEngine, engine_generator

    cfg = smoke_config(get_config("qwen3-0.6b")).with_overrides(dtype="float32")
    params = init_params(LM.param_specs(cfg), jax.random.PRNGKey(0))
    pol = ShardingPolicy(rules=base_rules(False), mesh=None)
    engine = ServeEngine(
        cfg, pol, params,
        ServeConfig(
            max_batch=max_batch, max_prompt_len=256, max_new_tokens=max_new_tokens,
            paged=paged, block_size=block_size, n_pool_blocks=pool_blocks,
            prefix_cache=prefix_cache, token_budget=token_budget,
            spill_bytes=spill_bytes, draft_k=draft_k, shards=shards,
        ),
    )
    return engine_generator(engine)


def parse_tenant_spec(spec: str) -> tuple[dict[str, float], dict[str, int]]:
    """``--tenants 'interactive=4:1,batch=1'`` -> (weights, priorities).

    Each comma-separated entry is ``name=weight[:priority]``; weight is
    the weighted-fair admission share within a priority class, priority
    the strict admission class (higher preempts the queue)."""
    weights: dict[str, float] = {}
    prios: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, eq, rest = part.partition("=")
        name = name.strip()
        if not name or not eq:
            raise ValueError(f"bad --tenants entry {part!r} (want name=weight[:priority])")
        w, _, p = rest.partition(":")
        weights[name] = float(w)
        prios[name] = int(p) if p else 0
    if not weights:
        raise ValueError(f"--tenants spec {spec!r} names no tenants")
    return weights, prios


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=5)
    ap.add_argument("--aggregation", default="rerank", choices=["embedding_rank", "rerank"])
    ap.add_argument("--n-facts", type=int, default=128)
    ap.add_argument("--m-local", type=int, default=8)
    ap.add_argument("--n-global", type=int, default=8)
    ap.add_argument("--kill-provider", type=int, default=None)
    ap.add_argument("--deadline-s", type=float, default=None, help="collect wall-clock cutoff")
    ap.add_argument(
        "--sequential-collect", action="store_true",
        help="disable concurrent provider fan-out (determinism baseline)",
    )
    ap.add_argument(
        "--generate", action="store_true",
        help="decode answers through the continuous-batching ServeEngine",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="pipelined front door: collect micro-batch N+1 overlaps decode "
        "of N, results print as each generation retires (implies --generate)",
    )
    ap.add_argument(
        "--collect-batch", type=int, default=4,
        help="micro-batch size of the --stream collector thread",
    )
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument(
        "--paged", action="store_true",
        help="paged KV cache: block-pool memory manager instead of one "
        "contiguous stripe per slot (admission becomes memory-aware)",
    )
    ap.add_argument("--block-size", type=int, default=32, help="tokens per KV block (--paged)")
    ap.add_argument(
        "--pool-blocks", type=int, default=None,
        help="KV pool size in blocks (--paged); default = max-batch contiguous stripes",
    )
    ap.add_argument("--max-batch", type=int, default=4, help="engine decode slots")
    ap.add_argument(
        "--prefix-cache", action="store_true",
        help="refcounted prefix cache on the paged pool: repeated prompt "
        "preambles (same aggregated context, retries) share KV blocks and "
        "skip their prefill (implies --paged --generate)",
    )
    ap.add_argument(
        "--token-budget", type=int, default=None, metavar="N",
        help="unified chunked prefill: one mixed prefill+decode dispatch "
        "per engine step, advancing at most N prompt tokens plus every "
        "live decode row — long prompts are spread across steps instead "
        "of stalling in-flight decodes, and dispatches stay O(1)/step "
        "(implies --paged --generate; composes with --prefix-cache)",
    )
    ap.add_argument(
        "--draft-k", type=int, default=0, metavar="K",
        help="speculative decoding: a resident drafter (self-speculation "
        "in the demo) proposes K greedy tokens per slot from its own "
        "paged pool; the target verifies all K+1 lanes in ONE mixed "
        "dispatch and greedy accept-prefix commits the matching run plus "
        "one correction token — outputs stay bit-identical to plain "
        "decode at up to K+1 tokens per target forward (implies --paged "
        "--generate; composes with --token-budget and --prefix-cache)",
    )
    ap.add_argument(
        "--shards", type=int, default=None, metavar="N",
        help="partition the paged KV pool over N mesh devices (row-affine "
        "blocks, one distributed mixed dispatch per step, bit-identical "
        "to --shards 1; implies --paged --generate).  Needs N devices; "
        "the README shows how a CPU host fakes them",
    )
    ap.add_argument(
        "--repeat", type=int, default=1,
        help="serve the query set N times through ONE resident "
        "engine+index (the repeat/retry traffic a prefix cache "
        "de-duplicates; prints the per-repeat hit-rate trajectory)",
    )
    ap.add_argument(
        "--tenants", type=str, default=None, metavar="SPEC",
        help="per-tenant SLO classes, e.g. 'interactive=4:1,batch=1' "
        "(name=weight[:priority]); queries are assigned round-robin and "
        "admission is strict-priority then weighted-fair (implies "
        "--generate); per-tenant latency/prefix gauges print at the end",
    )
    ap.add_argument(
        "--fifo", action="store_true",
        help="ignore tenant weights/priorities for admission ordering "
        "(global arrival-order baseline; tenants still tagged for stats)",
    )
    ap.add_argument(
        "--spill-mb", type=float, default=None, metavar="MB",
        help="host-RAM spill tier for the prefix cache, in MiB: parked "
        "chains evicted under pool pressure demote to host memory and "
        "re-admit by upload instead of re-prefill (implies --prefix-cache)",
    )
    ap.add_argument(
        "--fault-spec", type=str, default=None, metavar="JSON",
        help='seeded fault injection on every provider, e.g. '
        '\'{"seed": 0, "p_conn": 0.1, "p_corrupt": 0.05, "p_poison": 0.05}\' '
        "(see core.resilience.FaultSpec for the full taxonomy)",
    )
    ap.add_argument(
        "--retries", type=int, default=1,
        help="collect attempts per provider per round (exponential "
        "backoff, budget deducted from --deadline-s; 1 = off)",
    )
    ap.add_argument(
        "--breaker", action=argparse.BooleanOptionalAction, default=False,
        help="per-provider circuit breakers: a provider that fails "
        "consecutive rounds is skipped (no round-trip cost) until a "
        "cooldown expires (--no-breaker to force off)",
    )
    ap.add_argument(
        "--score-gate", action="store_true",
        help="aggregator-side poisoning gate: per-provider score "
        "calibration + outlier-round quarantine",
    )
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.spill_mb is not None:
        args.prefix_cache = True
    if (args.prefix_cache or args.token_budget is not None or args.draft_k > 0
            or args.shards is not None):
        args.paged = args.generate = True
    if args.tenants is not None:
        args.generate = True
    if args.stream:
        args.generate = True
    tenant_weights = tenant_prios = None
    if args.tenants is not None:
        tenant_weights, tenant_prios = parse_tenant_spec(args.tenants)

    corpus = make_federated_corpus(n_facts=args.n_facts, n_distractors=args.n_facts, n_queries=args.queries)
    tok = HashTokenizer()
    sys_ = CFedRAGSystem(
        corpus,
        CFedRAGConfig(
            aggregation=args.aggregation,
            m_local=args.m_local,
            n_global=args.n_global,
            deadline_s=args.deadline_s,
            concurrent_collect=False if args.sequential_collect else None,
            retries=args.retries,
            breaker=args.breaker,
            score_gate=args.score_gate,
        ),
        fault_spec=FaultSpec.from_json(args.fault_spec) if args.fault_spec else None,
        tokenizer=tok,
        reranker=overlap_reranker(tok) if args.aggregation == "rerank" else None,
        generator=make_demo_engine(
            args.max_new_tokens, paged=args.paged, block_size=args.block_size,
            pool_blocks=args.pool_blocks, max_batch=args.max_batch,
            prefix_cache=args.prefix_cache, token_budget=args.token_budget,
            spill_bytes=int(args.spill_mb * 2**20) if args.spill_mb else None,
            draft_k=args.draft_k, shards=args.shards,
        ) if args.generate else None,
    )
    if args.kill_provider is not None:
        sys_.providers[args.kill_provider].fail = True
        print(f"!! provider {args.kill_provider} marked down (quorum keeps serving)")

    texts = [q.text for q in corpus.queries[: args.queries]]
    qmeta = list(corpus.queries[: args.queries])
    tenants = priorities = None
    if tenant_weights is not None:
        names = list(tenant_weights)
        tenants = [names[i % len(names)] for i in range(len(texts))]
        priorities = [tenant_prios[t] for t in tenants]
    if args.generate:
        # warm the engine's jit paths (admit/decode-chunk) so the printed
        # per-request p50/p95 reflect serving latency, not compilation
        sys_.orchestrator.generator.engine.serve_prompts(
            [np.full((4,), 9, np.int32)], max_new_tokens=2
        )
    if args.deadline_s is not None:
        # readiness warm-up: the first collect jit-compiles the provider
        # embed path (seconds) — a deadline SLO applies to serving, not
        # to cold-start compilation
        orch = sys_.orchestrator
        orch.deadline_s = None
        orch.collect_contexts_batch(texts)
        orch.collect_contexts(texts[0])
        orch.deadline_s = args.deadline_s
    # --repeat loops over ONE resident system: the engine, block pool, and
    # prefix index survive across rounds, so round 2+ re-serves every
    # query against a warm index (guaranteed preamble hits) — the
    # per-repeat trajectory below is the CLI-visible proof
    results: list = []
    meta_all: list = []
    for rep in range(max(1, args.repeat)):
        if args.stream:
            # pipelined: results arrive in retire order while later
            # micro-batches are still collecting; print the stream live,
            # then report per-query below in submission order
            res = [None] * len(texts)
            for qidx, out in sys_.serve_stream(
                texts, max_new_tokens=args.max_new_tokens,
                collect_batch=args.collect_batch, tenants=tenants,
                priorities=priorities, tenant_weights=tenant_weights,
                fifo=args.fifo,
            ):
                res[qidx] = out
                print(
                    f"  [stream] q{qidx} retired: status={out['status']} "
                    f"lat={out['latency_s'] * 1e3:.1f}ms (collect->finish)"
                )
        elif args.generate:
            res = sys_.serve(
                texts, max_new_tokens=args.max_new_tokens, tenants=tenants,
                priorities=priorities, tenant_weights=tenant_weights,
                fifo=args.fifo,
            )
        else:
            res = [sys_.orchestrator.answer(t) for t in texts]
        results.extend(res)
        meta_all.extend(qmeta)
        if args.repeat > 1 and args.generate:
            st = getattr(sys_, "last_serve_stats", {})
            print(
                f"repeat {rep + 1}/{args.repeat}: prefix hits "
                f"{st.get('prefix_hits', 0)}/{st.get('prefix_lookups', 0)} "
                f"({st.get('prefix_hit_rate', 0.0):.0%}), "
                f"{st.get('prefill_tokens_saved', 0)} prefill tokens saved "
                "this round"
            )
    for q, res in zip(meta_all, results):
        if res.get("degraded"):
            print(
                f"Q: {q.text!r:45s} DEGRADED ({res['error']}) — "
                "flagged result, stream/batch kept serving"
            )
            continue
        ids = list(res["context"]["chunk_ids"])
        hit = q.gold_chunk_id in ids
        extra = ""
        if "answer_tokens" in res:
            extra = f" answer_toks={len(res['answer_tokens'])} lat={res['latency_s'] * 1e3:.1f}ms"
        print(
            f"Q: {q.text!r:45s} gold_chunk={q.gold_chunk_id:4d} "
            f"hit@{args.n_global}={'Y' if hit else 'n'} "
            f"providers={res['n_providers']} candidates={res['context']['n_candidates']}"
            + extra
        )
    if args.generate:
        lats = sorted(r["latency_s"] for r in results if r.get("latency_s") is not None)
        st = getattr(sys_, "last_serve_stats", {})
        if lats:
            p50 = lats[len(lats) // 2]
            p95 = lats[min(len(lats) - 1, int(len(lats) * 0.95))]
            line = f"\ngeneration latency: p50={p50 * 1e3:.1f}ms p95={p95 * 1e3:.1f}ms"
            if "ttft_p95_s" in st:
                line += (
                    f"; first token p50={st['ttft_p50_s'] * 1e3:.1f}ms "
                    f"p95={st['ttft_p95_s'] * 1e3:.1f}ms"
                )
            print(line)
        if "min_free_slots" in st:
            slots = sys_.orchestrator.generator.engine.scfg.max_batch
            line = (
                f"memory headroom: peak {slots - st['min_free_slots']}/{slots} slots "
                f"(backlog peak {st['peak_backlog']})"
            )
            if "min_free_blocks" in st:
                line += (
                    f", KV blocks {st['free_blocks']} free now / "
                    f"{st['min_free_blocks']} at peak ({args.block_size} tok/block)"
                )
            if args.shards is not None:
                line += f" over {args.shards} pool shard(s)"
            print(line)
            if args.draft_k > 0 and "draft_free_blocks" in st:
                print(
                    f"drafter pool: {st['draft_free_blocks']} blocks free now / "
                    f"{st['min_draft_free_blocks']} at peak"
                )
        if "engine_steps" in st and st["engine_steps"]:
            print(
                f"dispatches: {st['admit_dispatches']} admit + "
                f"{st['decode_dispatches']} decode + "
                f"{st['mixed_dispatches']} mixed over {st['engine_steps']} "
                f"engine steps ({st['dispatches_per_step']:.2f}/step)"
            )
        if "spec_tokens_per_round" in st:
            print(
                f"speculation: {st['spec_tokens_per_round']:.2f} tokens/round "
                f"at accept rate {st.get('spec_accept_rate', 0.0):.0%} "
                f"(draft_k={args.draft_k}), "
                f"{st['dispatches_per_spec_round']:.2f} dispatches/spec round "
                f"over {st['spec_rounds']} rounds"
            )
        if "prefix_lookups" in st:
            print(
                f"prefix cache: {st['prefix_hits']}/{st['prefix_lookups']} hits "
                f"({st.get('prefix_hit_rate', 0.0):.0%}), "
                f"{st['prefill_tokens_saved']}/{st['prefill_tokens']} prefill tokens "
                f"saved ({st.get('prefill_saved_frac', 0.0):.0%}), "
                f"{st['prefix_shared_blocks']} blocks shared by reference, "
                f"{st['prefix_cached_blocks']} chunks cached "
                f"({st.get('reclaimable_blocks', 0)} reclaimable)"
            )
        if "spilled_blocks" in st:
            print(
                f"spill tier: {st['spilled_blocks']} chunks on host "
                f"({st['spill_bytes_used'] / 2**20:.2f} MiB), "
                f"{st['spill_demotions']} demotions / "
                f"{st['spill_readmits']} re-admits this window"
            )
        for name, ts in sorted(st.get("tenants", {}).items()):
            line = (
                f"tenant {name}: {ts['n_done']} done, {ts['n_expired']} expired, "
                f"{ts.get('n_admitted', 0)} admitted, {ts['tokens_out']} tokens out"
            )
            if "p95_s" in ts:
                line += f", p50={ts['p50_s'] * 1e3:.1f}ms p95={ts['p95_s'] * 1e3:.1f}ms"
            if ts.get("prefix_lookups") and args.prefix_cache:
                line += f", prefix hit rate {ts.get('prefix_hit_rate', 0.0):.0%}"
            print(line)
    fed = sys_.orchestrator.federation_stats()
    tot = fed["totals"]
    if tot["attempts"]:
        print(
            f"federation: {tot['successes']}/{tot['attempts']} round-trips ok, "
            f"{tot['retries']} retries, {tot['skips']} breaker skips "
            f"({tot['breakers_open']} breakers open), "
            f"{tot['rechannels']} channel re-establishes, "
            f"faults conn={tot['faults']['conn']} timeout={tot['faults']['timeout']} "
            f"integrity={tot['faults']['integrity']}, "
            f"{tot['quarantined']} rounds quarantined by the score gate"
        )
        flaky = {
            pid: d for pid, d in fed["providers"].items()
            if d["attempts"] != d["successes"] or d["skips"] or d["quarantined"]
        }
        for pid, d in sorted(flaky.items()):
            print(
                f"  provider {pid}: {d['successes']}/{d['attempts']} ok, "
                f"{d['retries']} retries, {d['skips']} skips, "
                f"breaker={d['breaker'] or 'off'}, faults={d['faults']}"
                + (f", injected={d['injected']}" if "injected" in d else "")
            )
    stats = sys_.eval_retrieval(args.queries)
    print(f"\nrecall@{args.n_global}: {stats['recall_at_n']:.3f}  mrr: {stats['mrr']:.3f}")


if __name__ == "__main__":
    main()
