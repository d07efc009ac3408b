"""The program's spans (``repro.runtime.tracing``): nesting and parents,
self time, recording only while a profiler trace runs or inside
``recording()``, spans across ``serve_stream``'s yield, the engine's
first-token timestamps, and the federated round's device waits."""
import tempfile
import threading
import time

import jax
import pytest

from _fake_lm import make_fake_engine, prompt_ending
from repro.runtime import tracing
from repro.serving.scheduler import Scheduler


@pytest.fixture(autouse=True)
def empty_log():
    tracing.clear()
    yield
    tracing.clear()


def by_name(records, name):
    return [r for r in records if r.name == name]


def test_spans_nest_and_a_child_names_its_enclosing_span():
    with tracing.recording():
        with tracing.span("outer", n=1) as outer:
            with tracing.span("inner") as inner:
                with tracing.span("leaf") as leaf:
                    pass
            with tracing.span("inner"):
                pass
            assert tracing.current() is outer
        assert tracing.current() is None
    recs = {r.id: r for r in tracing.spans()}
    assert recs[outer.id].parent is None and recs[outer.id].attrs == {"n": 1}
    assert recs[inner.id].parent == outer.id and recs[leaf.id].parent == inner.id
    assert [r.parent for r in by_name(recs.values(), "inner")] == [outer.id, outer.id]
    for r in recs.values():
        if r.parent is not None:
            p = recs[r.parent]
            assert p.t0 <= r.t0 <= r.t1 <= p.t1


def test_self_time_is_the_duration_less_the_child_cover():
    with tracing.recording():
        with tracing.span("parent") as parent:
            time.sleep(0.03)
            for _ in range(2):
                with tracing.span("child"):
                    time.sleep(0.02)
    recs = tracing.spans()
    (p,) = by_name(recs, "parent")
    kids = [r for r in recs if r.parent == parent.id]
    assert len(kids) == 2 and kids[0].t1 <= kids[1].t0
    cover = sum(k.t1 - k.t0 for k in kids)
    assert cover >= 0.04e9
    assert (p.t1 - p.t0) - cover >= 0.03e9


def test_record_and_drop():
    with tracing.recording():
        with tracing.span("kept") as kept:
            tracing.record("measured", 5, 9, k=2)
        with tracing.span("dropped") as dropped:
            dropped.drop()
            with tracing.span("after_drop") as after:
                pass
    recs = tracing.spans()
    assert [r.name for r in recs] == ["measured", "kept", "after_drop"]
    assert recs[0].parent == kept.id and (recs[0].t0, recs[0].t1) == (5, 9)
    assert after.parent is None


def test_nothing_is_logged_off(monkeypatch):
    eng = make_fake_engine(monkeypatch, max_batch=2, max_new_tokens=4, paged=True,
                           block_size=4, token_budget=4)
    sched = Scheduler()
    sched.submit_many([prompt_ending(e, length=7) for e in (10, 20, 30)], 4)
    assert not tracing.enabled()
    assert len(dict(eng.serve_stream(sched, drain=True))) == 3
    assert tracing.spans() == []


def test_a_profiler_trace_turns_recording_on(monkeypatch):
    eng = make_fake_engine(monkeypatch, max_batch=2, max_new_tokens=4, paged=True,
                           block_size=4, token_budget=4)
    sched = Scheduler()
    sched.submit_many([prompt_ending(e, length=7) for e in (10, 20, 30)], 4)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            assert tracing.enabled()
            dict(eng.serve_stream(sched, drain=True))
        finally:
            jax.profiler.stop_trace()
    assert not tracing.enabled()
    names = {r.name for r in tracing.spans()}
    assert {"engine.step", "engine.dispatch", "engine.launch", "engine.readback",
            "engine.retire", "engine.yield", "req.prefill", "req.decode"} <= names


def test_a_span_open_around_the_stream_keeps_its_children(monkeypatch):
    """The consumer's spans opened between two results parent to the
    consumer's own span, not to the engine step suspended at its yield; an
    abandoned stream leaves the stack as it found it."""
    eng = make_fake_engine(monkeypatch, max_batch=2, max_new_tokens=4, paged=True,
                           block_size=4, token_budget=4)
    sched = Scheduler()
    sched.submit_many([prompt_ending(e, length=7) for e in (10, 20, 30, 40)], 4)
    with tracing.recording():
        with tracing.span("consumer") as consumer:
            stream = eng.serve_stream(sched, drain=True)
            for n, _ in enumerate(stream):
                with tracing.span("handle") as handle:
                    assert handle.parent == consumer.id
                assert tracing.current() is consumer
                if n == 1:
                    stream.close()  # abandoned with work still queued
                    break
            assert tracing.current() is consumer
        assert tracing.current() is None
    recs = {r.id: r for r in tracing.spans()}
    steps = by_name(recs.values(), "engine.step")
    assert steps and all(s.parent == consumer.id for s in steps)
    yields = by_name(recs.values(), "engine.yield")
    assert len(yields) == 2 and all(recs[y.parent].name == "engine.step" for y in yields)
    for h in by_name(recs.values(), "handle"):
        assert h.parent == consumer.id
    for name in ("engine.dispatch", "engine.retire"):
        assert all(recs[r.parent].name == "engine.step" for r in by_name(recs.values(), name))
    for name in ("engine.launch", "engine.readback"):
        assert all(recs[r.parent].name == "engine.dispatch" for r in by_name(recs.values(), name))


@pytest.mark.parametrize("path", [
    dict(paged=True, block_size=4, token_budget=4),
    dict(paged=True, block_size=4, token_budget=6, draft_k=2),
    dict(paged=False),
], ids=["unified", "speculative", "contiguous"])
def test_first_token_lies_between_admission_and_finish(monkeypatch, path):
    eng = make_fake_engine(monkeypatch, max_batch=2, max_new_tokens=6, sched_chunk=2, **path)
    sched = Scheduler()
    ends, budgets = [250, 0, 10, 253, 99, 30], [6, 3, 2, 6, 1, 4]
    lengths = [8, 7, 5, 8, 6, 7]
    rids = sched.submit_many([prompt_ending(e, n) for e, n in zip(ends, lengths)], budgets)
    with tracing.recording():
        res = eng.serve(sched)
    assert set(res) == set(rids)
    prefill = {r.attrs["rid"]: r for r in by_name(tracing.spans(), "req.prefill")}
    decode = {r.attrs["rid"]: r for r in by_name(tracing.spans(), "req.decode")}
    for rid in rids:
        req = sched.results[rid]
        assert req.status == "done"
        assert req.started_at <= req.first_token_at <= req.finished_at
        assert prefill[rid].t1 == decode[rid].t0 == int(req.first_token_at * 1e9)
        assert decode[rid].attrs["tokens"] == len(res[rid])
    st = sched.latency_stats()
    assert 0 < st["ttft_p50_s"] <= st["ttft_p95_s"] <= st["p95_s"]
    if path["paged"] and "draft_k" not in path:
        # an 8-token prompt through 4-lane steps: its first token comes at
        # the read-back of the second step that carries it, not the first
        long = [rid for rid, n in zip(rids, lengths) if n == 8]
        launches = [r for r in tracing.spans() if r.name == "engine.dispatch"]
        for rid in long:
            req = sched.results[rid]
            carried = [d for d in launches if rid in d.attrs["rids"]
                       and d.t0 >= int(req.started_at * 1e9)
                       and d.t1 <= int(req.first_token_at * 1e9)]
            assert len(carried) >= 2


def test_a_mixed_dispatch_counts_the_kv_blocks_its_attention_walks(monkeypatch):
    """``kv_blocks_live`` sums ``cdiv(q_start + q_len, block_size)`` over
    the rows with ``q_len > 0`` (a decode row's ``q_start`` is ``lengths +
    emitted - 1``); ``kv_blocks_grid`` is ``max_batch`` x the table width."""
    eng = make_fake_engine(monkeypatch, max_batch=3, max_new_tokens=4, paged=True,
                           block_size=4, token_budget=4)
    sched = Scheduler()
    sched.submit_many([prompt_ending(30, length=7), prompt_ending(40, length=8)], 4)
    with tracing.recording():
        eng.serve(sched)
    mixed = [r.attrs for r in by_name(tracing.spans(), "engine.dispatch")
             if r.attrs["kind"] == "mixed"]
    # the first step with a decode row: slot 0 decodes its first answer token
    # at position 7 (kv 8: 2 blocks), slot 1 fills prompt positions 1-3 of 8
    # (kv 4: 1 block), slot 2 is empty (no block)
    d = next(a for a in mixed if a["is_decode"].any())
    assert list(d["is_decode"]) == [True, False, False]
    assert list(d["q_len"]) == [1, 3, 0] and d["rids"][2] == -1
    assert d["q_start"][1] + d["q_len"][1] < d["row_len"][1]
    assert d["kv_blocks_live"] == 2 + 1
    assert all(a["kv_blocks_grid"] == 3 * 3 for a in mixed)  # 12 cache tokens / 4


@pytest.fixture(scope="module")
def federation():
    from repro.core.pipeline import CFedRAGConfig, CFedRAGSystem
    from repro.data.corpus import make_federated_corpus

    corpus = make_federated_corpus(n_facts=24, n_distractors=24, n_queries=4, seed=5)
    sys_ = CFedRAGSystem(corpus, CFedRAGConfig(split_by="corpus", m_local=4, n_global=4,
                                               chunk_max_len=16))
    assert len(sys_.providers) == 4
    return corpus, sys_


@pytest.mark.parametrize("concurrent", [False, True], ids=["sequential", "fan-out"])
def test_a_round_records_two_device_waits_per_provider(federation, concurrent):
    corpus, sys_ = federation
    orch = sys_.orchestrator
    orch.concurrent_collect = concurrent
    texts = [q.text for q in corpus.queries[:2]]
    orch.collect_contexts_batch(texts)  # compile outside the log
    tracing.clear()
    with tracing.recording():
        responses = orch.collect_contexts_batch(texts)
        contexts = orch.aggregate_batch(texts, responses)
        orch.build_prompt(texts[0], contexts[0])
    orch.concurrent_collect = None
    recs = {r.id: r for r in tracing.spans()}
    (collect,) = by_name(recs.values(), "fed.collect")
    assert collect.attrs == {"round": orch.rounds, "batch": 2, "providers": 4}
    requests = by_name(recs.values(), "provider.request")
    assert sorted(r.attrs["provider"] for r in requests) == [0, 1, 2, 3]
    assert all(r.parent == collect.id and r.attrs["round"] == orch.rounds for r in requests)
    waits = [r for r in recs.values() if r.name.endswith(".wait")]
    assert len(waits) == 8
    assert sorted(r.name for r in waits) == ["provider.embed.wait"] * 4 + ["provider.topk.wait"] * 4
    assert all(recs[w.parent].name == "provider.request" for w in waits)
    if concurrent:
        assert threading.current_thread().name not in {r.thread for r in requests}
    for name in ("fed.aggregate", "fed.prompt"):
        (r,) = by_name(recs.values(), name)
        assert r.attrs == {"round": orch.rounds}


def test_each_dispatch_of_a_held_expert_model_carries_its_routing_counters(monkeypatch):
    """A model whose MoE layers hold experts returns its routing counters
    from both step programs; the engine attaches them to each
    ``engine.dispatch`` span: the (token, held expert) pairs of the live
    tokens, the held experts they touched and the most pairs one expert
    took, each summed over the MoE layers (and a decode chunk's steps).
    A dense model's dispatches carry none."""
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.models import lm as LM
    from repro.models.moe import ROUTING_COUNTERS
    from repro.models.params import init_params
    from repro.runtime.sharding import ShardingPolicy, base_rules
    from repro.serving.engine import ServeConfig, ServeEngine

    cfg = ModelConfig(
        name="tiny-mla-moe", family="moe", n_layers=3, d_model=32, n_heads=2, n_kv_heads=1,
        d_ff=64, vocab_size=128, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, n_experts=8, moe_top_k=3, moe_d_ff=16, n_shared_experts=1,
        experts_held=4, expert_offset=2, moe_scale=2.0, first_dense=1, dtype="float32")
    params = init_params(LM.param_specs(cfg), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, ShardingPolicy(rules=base_rules(False), mesh=None), params,
                      ServeConfig(max_batch=2, max_prompt_len=16, max_new_tokens=6, paged=True,
                                  block_size=4, token_budget=8))
    sched = Scheduler()
    sched.submit_many([np.arange(1, 11, dtype=np.int32), np.arange(20, 26, dtype=np.int32)], 6)
    with tracing.recording():
        eng.serve(sched)
    disp = by_name(tracing.spans(), "engine.dispatch")
    assert {d.attrs["kind"] for d in disp} == {"mixed", "decode"}
    n_moe, k, held = 2, 3, 4
    for d in disp:
        a = d.attrs
        pairs, touched, most = (a[n] for n in ROUTING_COUNTERS)
        if a["kind"] == "mixed":
            live = int(np.sum(np.where(a["is_decode"] & a["done"], 0, a["q_len"])))
            steps = 1
        else:
            live = int(np.sum(a["emitted_after"] - a["emitted"]))
            steps = a["n_steps"]
        assert 0 <= pairs <= live * n_moe * k
        assert touched <= held * n_moe * steps and most <= pairs <= most * max(touched, 1)
        assert (pairs == 0) == (touched == 0)
    assert sum(d.attrs["moe_pairs_held"] for d in disp) > 0
    # a model without held experts returns no counters and its spans carry none
    tracing.clear()
    eng = make_fake_engine(monkeypatch, max_batch=2, max_new_tokens=3, paged=True,
                           block_size=4, token_budget=4)
    sched = Scheduler()
    sched.submit_many([prompt_ending(30, length=6)], 3)
    with tracing.recording():
        eng.serve(sched)
    dense = by_name(tracing.spans(), "engine.dispatch")
    assert dense and not any(n in d.attrs for d in dense for n in ROUTING_COUNTERS)
