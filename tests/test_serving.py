"""Serving engine + dry-run cell smoke (small mesh).

Includes the paged-KV acceptance suite: the block-pool engine must be
bit-identical to the contiguous baseline for the same admission order,
degrade a request to early-retire (never corrupt a neighbor) on pool
OOM, and admit more concurrent slots than the contiguous stripe count
at equal HBM on short-prompt traffic."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _fake_lm import expected_answer, make_fake_engine, prompt_ending
from repro.configs import get_config, smoke_config
from repro.data.tokenizer import HashTokenizer
from repro.models import lm as LM
from repro.models.params import init_params
from repro.runtime.sharding import ShardingPolicy, base_rules
from repro.serving.engine import ServeConfig, ServeEngine
from repro.serving.scheduler import Scheduler

POL = ShardingPolicy(rules=base_rules(False), mesh=None)


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config(get_config("qwen3-0.6b")).with_overrides(dtype="float32")
    params = init_params(LM.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def test_engine_matches_direct_generate(small_lm):
    cfg, params = small_lm
    scfg = ServeConfig(max_batch=2, max_prompt_len=16, max_new_tokens=4)
    eng = ServeEngine(cfg, POL, params, scfg)
    rng = np.random.default_rng(0)
    p1 = rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
    p2 = rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
    eng.submit(p1)
    eng.submit(p2)
    outs = eng.step_batch()
    assert len(outs) == 2
    direct = LM.generate(cfg, POL, params, {"tokens": jnp.stack([jnp.asarray(p1), jnp.asarray(p2)])}, n_tokens=4)
    for got, want in zip(outs, np.asarray(direct)):
        assert (got[: len(want)] == want).all(), "batched serving diverged from generate()"


def test_engine_ragged_batch_matches_single(small_lm):
    """Per-row decode positions: a ragged batch must produce the same
    tokens as serving each prompt alone (same packing width), i.e. short
    rows decode from their own cache slot and never attend to PAD kv."""
    cfg, params = small_lm
    scfg = ServeConfig(max_batch=3, max_prompt_len=16, max_new_tokens=4)
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(8, cfg.vocab_size, size=n).astype(np.int32) for n in (10, 16, 13)
    ]
    eng = ServeEngine(cfg, POL, params, scfg)
    for p in prompts:
        eng.submit(p)
    batched = eng.step_batch()
    assert len(batched) == 3
    solo_eng = ServeEngine(
        cfg, POL, params, ServeConfig(max_batch=1, max_prompt_len=16, max_new_tokens=4)
    )
    for p, got in zip(prompts, batched):
        solo_eng.submit(p)
        want = solo_eng.step_batch()[0]
        n = min(len(got), len(want))
        assert (got[:n] == want[:n]).all(), "ragged row diverged from solo decode"


def test_engine_queue_drains(small_lm):
    cfg, params = small_lm
    eng = ServeEngine(cfg, POL, params, ServeConfig(max_batch=2, max_prompt_len=8, max_new_tokens=2))
    for _ in range(5):
        eng.submit(np.arange(1, 9, dtype=np.int32))
    served = 0
    while eng.queue:
        served += len(eng.step_batch())
    assert served == 5  # 2 + 2 + 1
    assert eng.step_batch() == []  # drained


# ------------------------------------------------------------------ #
# paged KV cache: bit-parity with the contiguous baseline (real LM)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_paged_matches_contiguous_bitwise(small_lm, block_size):
    """Acceptance: for the same admission order, the paged engine must
    produce the contiguous engine's tokens BIT-IDENTICALLY on a ragged
    prompt/budget workload — same admission order, same masked-softmax
    lane count (cache_len here is a multiple of every tested block
    size), only the K/V storage layout and dispatch shape differ."""
    cfg, params = small_lm
    base_kw = dict(max_batch=2, max_prompt_len=11, max_new_tokens=5, sched_chunk=2)
    rng = np.random.default_rng(42)
    prompts = [
        rng.integers(8, cfg.vocab_size, size=n).astype(np.int32)
        for n in (9, 11, 6, 3, 11, 7)
    ]
    budgets = [5, 1, 4, 5, 2, 5]
    base = ServeEngine(cfg, POL, params, ServeConfig(**base_kw))
    want = base.serve_prompts(prompts, max_new_tokens=budgets)
    paged = ServeEngine(
        cfg, POL, params, ServeConfig(paged=True, block_size=block_size, **base_kw)
    )
    got = paged.serve_prompts(prompts, max_new_tokens=budgets)
    for i, (w, g) in enumerate(zip(want, got)):
        assert np.array_equal(w, g), f"prompt {i}: paged {list(g)} != contiguous {list(w)}"


def test_paged_more_slots_than_stripes_same_hbm(small_lm):
    """The point of paging: with the HBM of 2 contiguous stripes, a paged
    engine with 4 slots serves short prompts 4-at-a-time — concurrency is
    bounded by resident tokens, not worst-case stripes — and the answers
    still match the contiguous engine bit-for-bit."""
    cfg, params = small_lm
    bs = 4
    kw = dict(max_prompt_len=12, max_new_tokens=4, sched_chunk=2)
    stripes = -(-(12 + 4) // bs)  # blocks per contiguous stripe
    base = ServeEngine(cfg, POL, params, ServeConfig(max_batch=2, **kw))
    paged = ServeEngine(
        cfg, POL, params,
        ServeConfig(max_batch=4, paged=True, block_size=bs, n_pool_blocks=2 * stripes, **kw),
    )
    assert paged.cache_nbytes() <= base.cache_nbytes() * (1 + 1 / (2 * stripes)) + 1
    rng = np.random.default_rng(5)
    prompts = [rng.integers(8, cfg.vocab_size, size=6).astype(np.int32) for _ in range(8)]
    sched = Scheduler()
    sched.submit_many(prompts, 3)
    res = paged.serve(sched)
    want = base.serve_prompts(prompts, max_new_tokens=3)
    for rid, w in enumerate(want):
        assert np.array_equal(res[rid], w)
    st = sched.latency_stats()
    # short prompts (6+3 tokens = 3 blocks) pack 4 concurrent requests
    # into 2 stripes' worth of blocks: strictly more than the stripe count
    assert paged.scfg.max_batch - st["min_free_slots"] > base.scfg.max_batch
    assert st["min_free_blocks"] >= 0


# ------------------------------------------------------------------ #
# paged KV cache: OOM + allocator lifecycle semantics (FakeLM, exact)
# ------------------------------------------------------------------ #
def test_paged_oom_retires_early_without_corruption(monkeypatch):
    """Two requests whose full budgets need 6 blocks contend for a
    4-block pool: whichever row hits the dry pool first retires early
    with an exact closed-form PREFIX and the ``truncated`` marker — a
    failed allocation truncates its own request and can never corrupt
    the neighbor's tokens.  The neighbor inherits the freed blocks and
    is allowed to finish its full budget (pool recycling, not fate
    sharing)."""
    # cache_len = 8+6 = 14 -> 4 blocks of 4 per worst-case request
    eng = make_fake_engine(
        monkeypatch, max_batch=2, max_new_tokens=6, sched_chunk=3,
        paged=True, block_size=4, n_pool_blocks=4,
    )
    ends = (10, 20)
    sched = Scheduler()
    rids = sched.submit_many([prompt_ending(e, 5) for e in ends], [6, 6])
    res = eng.serve(sched)
    for e, rid in zip(ends, rids):
        got, full = res[rid], expected_answer(e, 6)
        assert 1 <= len(got) <= len(full), "pool pressure must truncate, not kill"
        assert list(got) == full[: len(got)], f"end={e}: corrupted prefix {list(got)}"
        # OOM truncation is flagged, not silent: status stays terminal
        # "done" and short answers carry the degradation marker exactly
        assert sched.results[rid].status == "done"
        assert sched.results[rid].truncated == (len(got) < len(full))
    assert 1 <= sched.latency_stats()["n_truncated"] <= 2
    assert any(len(res[rid]) < 6 for rid in rids), "pool never ran dry?"


def test_paged_blocks_recycle_across_requests(monkeypatch):
    """Retired requests return their blocks; a long FIFO stream through a
    small pool must serve every request exactly (blocks recycle) while
    strict FIFO admission holds the line when the pool is full."""
    eng = make_fake_engine(
        monkeypatch, max_batch=3, max_new_tokens=4, sched_chunk=2,
        paged=True, block_size=4, n_pool_blocks=4,  # one worst-case request
    )
    ends = [250, 0, 10, 253, 99, 1, 200, 30]
    budgets = [4, 3, 2, 4, 1, 4, 2, 3]
    sched = Scheduler()
    rids = sched.submit_many([prompt_ending(e) for e in ends], budgets)
    res = eng.serve(sched)
    for e, b, rid in zip(ends, budgets, rids):
        assert list(res[rid]) == expected_answer(e, b), f"end={e} budget={b}"
    # requests WAITED for blocks (FIFO gate) rather than truncating:
    # a normal completion never reads as truncated
    assert sched.latency_stats()["n_truncated"] == 0


def test_paged_admit_reserves_first_decode_block(monkeypatch):
    """Regression: the admission gate checks free blocks for prompt+1
    tokens, so admit must RESERVE that much.  Three block-aligned prompts
    into a pool with room for two must admit exactly two (the third
    waits, strict FIFO) — not admit all three under-reserved and then
    force-truncate at the first chunk boundary."""
    # cache_len = 8+2 = 10 -> blocks_per_slot ceil(10/4) = 3 <= pool 4
    eng = make_fake_engine(
        monkeypatch, max_batch=3, max_new_tokens=2, sched_chunk=2,
        paged=True, block_size=4, n_pool_blocks=4,
    )
    ends = (10, 20, 30)
    sched = Scheduler()
    rids = sched.submit_many([prompt_ending(e, 4) for e in ends], 2)
    res = eng.serve(sched)
    for e, rid in zip(ends, rids):
        assert list(res[rid]) == expected_answer(e, 2), f"end={e}: {list(res[rid])}"
    st = sched.latency_stats()
    assert st["n_truncated"] == 0, "under-reserved admits truncated instead of waiting"
    # pool holds 2 x blocks_for(4+1)=2: the third request waited its turn
    assert st["min_free_slots"] == 1


def test_paged_pool_must_fit_one_request(monkeypatch):
    with pytest.raises(ValueError, match="cannot hold one max-size request"):
        make_fake_engine(monkeypatch, paged=True, block_size=4, n_pool_blocks=2)


# ------------------------------------------------------------------ #
# refcounted prefix cache: bit-parity + sharing semantics
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_prefix_shared_matches_unshared_bitwise(small_lm, block_size):
    """Acceptance: prefix-shared serving must be BIT-identical to the
    non-shared paged path for the same admission order on a ragged
    prompt/budget workload that mixes cold prompts, partial-prefix hits,
    same-pass identical siblings, and a full-prefix hit whose length is a
    multiple of every tested block size (the COW boundary-block case:
    the last prompt token's K/V write lands in a shared block and must
    go through a private copy, never mutate it)."""
    cfg, params = small_lm
    # width fits every prompt whole: a window tail-slice would shift the
    # preamble off block alignment and (correctly) turn hits into misses
    base_kw = dict(max_batch=2, max_prompt_len=20, max_new_tokens=5, sched_chunk=2)
    rng = np.random.default_rng(42)
    pre = rng.integers(8, cfg.vocab_size, size=16).astype(np.int32)  # 16 % {4,8,16} == 0
    tails = [rng.integers(8, cfg.vocab_size, size=n).astype(np.int32) for n in (1, 3, 2)]
    prompts = [
        np.concatenate([pre, tails[0]]),  # cold: inserts the preamble chunks
        np.concatenate([pre, tails[1]]),  # same-pass sibling: shares them
        pre.copy(),                        # full-prefix hit -> COW boundary block
        rng.integers(8, cfg.vocab_size, size=9).astype(np.int32),  # unrelated cold
        pre.copy(),                        # COW again, now against a parked chain
        np.concatenate([pre, tails[2]]),
    ]
    budgets = [5, 1, 4, 5, 2, 3]
    base = ServeEngine(
        cfg, POL, params, ServeConfig(paged=True, block_size=block_size, **base_kw)
    )
    want = base.serve_prompts(prompts, max_new_tokens=budgets)
    shared = ServeEngine(
        cfg, POL, params,
        ServeConfig(paged=True, prefix_cache=True, block_size=block_size, **base_kw),
    )
    got = shared.serve_prompts(prompts, max_new_tokens=budgets)
    for i, (w, g) in enumerate(zip(want, got)):
        assert np.array_equal(w, g), f"prompt {i}: shared {list(g)} != unshared {list(w)}"
    assert shared.prefix_lookups == len(prompts)
    assert shared.prefix_hits >= 3  # sibling + both full-prefix hits
    assert shared.prefill_tokens_saved > 0


def test_prefix_cache_gauges_and_savings(small_lm):
    """The hit-rate / tokens-saved gauges must surface through
    ``Scheduler.latency_stats`` and count real sharing: 4 prompts with a
    common 8-token preamble (block size 4) skip the preamble prefill on
    every hit."""
    cfg, params = small_lm
    eng = ServeEngine(
        cfg, POL, params,
        ServeConfig(max_batch=2, max_prompt_len=12, max_new_tokens=3,
                    sched_chunk=2, paged=True, prefix_cache=True, block_size=4),
    )
    rng = np.random.default_rng(3)
    pre = rng.integers(8, cfg.vocab_size, size=8).astype(np.int32)
    prompts = [
        np.concatenate([pre, rng.integers(8, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (2, 3, 1, 4)
    ]
    sched = Scheduler()
    sched.submit_many(prompts, 3)
    eng.serve(sched)
    st = sched.latency_stats()
    assert st["prefix_lookups"] == 4 and st["prefix_hits"] == 3
    assert st["prefix_hit_rate"] == pytest.approx(0.75)
    assert st["prefill_tokens_saved"] == 3 * len(pre)
    assert st["prefill_tokens"] == sum(len(p) for p in prompts)
    assert st["prefill_saved_frac"] == pytest.approx(
        3 * len(pre) / sum(len(p) for p in prompts)
    )
    assert st["prefix_cached_blocks"] >= 2 and st["prefix_shared_blocks"] >= 6
    assert "reclaimable_blocks" in st


def test_prefix_cache_recycles_and_evicts_exactly(monkeypatch):
    """FIFO stream of repeated + distinct prompts through a pool too
    small to cache everything: every answer must stay exact (eviction
    only ever recycles zero-ref parked blocks; live chains are pinned by
    their refcounts) and nothing may truncate — pressure is absorbed by
    the LRU sweep, not by degrading requests."""
    eng = make_fake_engine(
        monkeypatch, max_batch=3, max_new_tokens=4, sched_chunk=2,
        paged=True, block_size=4, n_pool_blocks=6, prefix_cache=True,
    )
    ends = [250, 250, 10, 250, 99, 10, 250, 30, 99]
    budgets = [4, 3, 2, 4, 1, 4, 2, 3, 2]
    sched = Scheduler()
    rids = sched.submit_many([prompt_ending(e, 8) for e in ends], budgets)
    res = eng.serve(sched)
    for e, b, rid in zip(ends, budgets, rids):
        assert list(res[rid]) == expected_answer(e, b), f"end={e} budget={b}"
    st = sched.latency_stats()
    assert st["n_truncated"] == 0
    assert st["prefix_hits"] > 0  # repeats actually shared


def test_prefix_cache_config_validation(small_lm):
    cfg, params = small_lm
    with pytest.raises(ValueError, match="requires paged"):
        ServeEngine(cfg, POL, params, ServeConfig(prefix_cache=True, paged=False))
    ssm = smoke_config(get_config("mamba2-1.3b")).with_overrides(dtype="float32")
    with pytest.raises(ValueError, match="all-attention"):
        ServeEngine(ssm, POL, {}, ServeConfig(prefix_cache=True, paged=True))
    with pytest.raises(ValueError, match="all-attention"):
        ServeEngine(ssm, POL, {}, ServeConfig(paged=True))
    # EVERY paged engine runs the unified chunked-prefill path now —
    # including the configs the retired dense+suffix pipeline could not
    # serve (pallas attention, prompts beyond attn_chunk, non-f32 caches)
    for c, kw in [
        (cfg, dict(max_prompt_len=16)),
        (cfg.with_overrides(attn_chunk=8), dict(max_prompt_len=16)),
        (cfg.with_overrides(attn_impl="pallas"), {}),
        (cfg.with_overrides(dtype="bfloat16"), dict(max_prompt_len=16)),
    ]:
        eng = ServeEngine(c, POL, params, ServeConfig(prefix_cache=True, paged=True, **kw))
        assert eng._unified, "paged engines must always run the unified path"
    # token_budget defaults on for paged engines (whole-prompt lanes)
    eng = ServeEngine(cfg, POL, params, ServeConfig(paged=True, max_prompt_len=16))
    assert eng._unified and eng._token_budget == 16
    # explicit token_budget has its own preconditions
    with pytest.raises(ValueError, match="requires.*paged"):
        ServeEngine(cfg, POL, params, ServeConfig(token_budget=8, paged=False))
    with pytest.raises(ValueError, match="token_budget"):
        ServeEngine(cfg, POL, params, ServeConfig(token_budget=0, paged=True))
    # host spill tier requires the prefix cache under it
    with pytest.raises(ValueError, match="spill_bytes"):
        ServeEngine(cfg, POL, params, ServeConfig(paged=True, spill_bytes=1 << 20))
    with pytest.raises(ValueError, match="spill_bytes"):
        ServeEngine(
            cfg, POL, params,
            ServeConfig(paged=True, prefix_cache=True, spill_bytes=0),
        )


# ------------------------------------------------------------------ #
# bucketed admission (applies to both cache layouts)
# ------------------------------------------------------------------ #
def test_bucketed_admission_dispatch_count(monkeypatch):
    """k requests waiting for k free slots must prefill in O(log k)
    power-of-2 fused dispatches, not k: 8 requests into 8 free slots is
    ONE dispatch of 8 rows; answers stay exact."""
    eng = make_fake_engine(monkeypatch, max_batch=8, max_new_tokens=4, sched_chunk=2)
    ends = [250, 0, 10, 253, 99, 1, 200, 30]
    outs = eng.serve_prompts([prompt_ending(e) for e in ends])
    assert eng.admit_rows_total == 8
    assert eng.admit_dispatches == 1, "8 simultaneous admits must fuse into one prefill"
    for e, got in zip(ends, outs):
        assert list(got) == expected_answer(e, 4)

    eng2 = make_fake_engine(monkeypatch, max_batch=4, max_new_tokens=4, sched_chunk=2)
    eng2.serve_prompts([prompt_ending(e) for e in (250, 0, 10)])
    # 3 waiting -> pow2 buckets 2 + 1
    assert eng2.admit_rows_total == 3 and eng2.admit_dispatches == 2


# ------------------------------------------------------------------ #
# unified chunked prefill: one mixed dispatch per engine step
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_unified_matches_contiguous_oracle_bitwise(small_lm, block_size):
    """Acceptance: for the same admission order, the unified token-budget
    engine must produce the CONTIGUOUS oracle's tokens BIT-IDENTICALLY on
    a ragged prompt/budget workload — prompts chunk across steps (budget
    3 splits every prompt) and decode rides the same dispatches, yet
    every emitted token matches the dedicated-stripe baseline."""
    cfg, params = small_lm
    base_kw = dict(max_batch=2, max_prompt_len=11, max_new_tokens=5, sched_chunk=2)
    rng = np.random.default_rng(42)
    prompts = [
        rng.integers(8, cfg.vocab_size, size=n).astype(np.int32)
        for n in (9, 11, 6, 3, 11, 7)
    ]
    budgets = [5, 1, 4, 5, 2, 5]
    oracle = ServeEngine(cfg, POL, params, ServeConfig(**base_kw))
    want = oracle.serve_prompts(prompts, max_new_tokens=budgets)
    for tb in (3, 11):
        uni = ServeEngine(
            cfg, POL, params,
            ServeConfig(paged=True, block_size=block_size, token_budget=tb, **base_kw),
        )
        got = uni.serve_prompts(prompts, max_new_tokens=budgets)
        for i, (w, g) in enumerate(zip(want, got)):
            assert np.array_equal(w, g), (
                f"tb={tb} prompt {i}: unified {list(g)} != contiguous {list(w)}"
            )
        assert uni.admit_dispatches == 0 and uni.mixed_dispatches > 0


@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_unified_prefix_shared_matches_contiguous_oracle_bitwise(small_lm, block_size):
    """Prefix sharing through the unified path (host-ordered pending
    chunks) must reproduce the CONTIGUOUS oracle bit-for-bit on a COW +
    sibling workload — cold prompts, a same-pass sibling that waits on
    pending chunks, full-prefix hits crossing the COW boundary block —
    and still actually share (hits, tokens saved)."""
    cfg, params = small_lm
    base_kw = dict(max_batch=2, max_prompt_len=20, max_new_tokens=5, sched_chunk=2)
    rng = np.random.default_rng(42)
    pre = rng.integers(8, cfg.vocab_size, size=16).astype(np.int32)
    tails = [rng.integers(8, cfg.vocab_size, size=n).astype(np.int32) for n in (1, 3, 2)]
    prompts = [
        np.concatenate([pre, tails[0]]),
        np.concatenate([pre, tails[1]]),  # same-pass sibling: waits on pending chunks
        pre.copy(),                        # full-prefix hit -> COW boundary block
        rng.integers(8, cfg.vocab_size, size=9).astype(np.int32),
        pre.copy(),
        np.concatenate([pre, tails[2]]),
    ]
    budgets = [5, 1, 4, 5, 2, 3]
    oracle = ServeEngine(cfg, POL, params, ServeConfig(**base_kw))
    want = oracle.serve_prompts(prompts, max_new_tokens=budgets)
    uni = ServeEngine(
        cfg, POL, params,
        ServeConfig(paged=True, prefix_cache=True, block_size=block_size,
                    token_budget=7, **base_kw),
    )
    got = uni.serve_prompts(prompts, max_new_tokens=budgets)
    for i, (w, g) in enumerate(zip(want, got)):
        assert np.array_equal(w, g), f"prompt {i}: shared {list(g)} != contiguous {list(w)}"
    assert uni.prefix_hits >= 3 and uni.prefill_tokens_saved > 0


def test_unified_lifts_dense_pipeline_restrictions(small_lm):
    """The configs the retired dense+suffix pipeline could not serve —
    pallas attention and prompts longer than attn_chunk — must serve
    through the unified path with hit-vs-miss bit-parity (cold and warm
    rows both attend through the pool, so sharing cannot change tokens)."""
    cfg, params = small_lm
    base_kw = dict(max_batch=2, max_prompt_len=20, max_new_tokens=4, sched_chunk=2,
                   paged=True, block_size=8)
    rng = np.random.default_rng(11)
    pre = rng.integers(8, cfg.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([pre, rng.integers(8, cfg.vocab_size, size=n).astype(np.int32)])
               for n in (2, 3, 1)]
    for c in (cfg.with_overrides(attn_impl="pallas"), cfg.with_overrides(attn_chunk=8)):
        hit_eng = ServeEngine(c, POL, params, ServeConfig(prefix_cache=True, **base_kw))
        assert hit_eng._unified
        hit = hit_eng.serve_prompts(prompts, max_new_tokens=4)
        miss = ServeEngine(
            c, POL, params,
            ServeConfig(token_budget=hit_eng._token_budget, **base_kw),
        ).serve_prompts(prompts, max_new_tokens=4)
        for i, (h, m) in enumerate(zip(hit, miss)):
            assert np.array_equal(h, m), f"prompt {i}: hit {list(h)} != miss {list(m)}"
        assert hit_eng.prefix_hits >= 2


def test_unified_dispatch_count_o1_per_step(monkeypatch):
    """Regression: the unified engine must issue exactly ONE device
    dispatch per engine step — no per-admit prefill calls (k admits in a
    pass cost 0 admit dispatches, vs O(log k) legacy) — and the mixed
    step must compile to a single jit trace (static shapes)."""
    eng = make_fake_engine(
        monkeypatch, max_batch=8, max_new_tokens=4, sched_chunk=2,
        paged=True, block_size=4, token_budget=4,
    )
    ends = [250, 0, 10, 253, 99, 1, 200, 30]
    sched = Scheduler()
    rids = sched.submit_many([prompt_ending(e) for e in ends], 4)
    res = eng.serve(sched)
    for e, rid in zip(ends, rids):
        assert list(res[rid]) == expected_answer(e, 4)
    assert eng.admit_dispatches == 0, "unified path must not dispatch admit prefills"
    assert eng.mixed_dispatches > 0
    st = sched.latency_stats()
    assert st["engine_steps"] == st["mixed_dispatches"] + st["decode_dispatches"]
    assert st["dispatches_per_step"] == 1.0
    cache_size = getattr(eng._mixed_rows, "_cache_size", None)
    if cache_size is not None:  # jax-version-dependent introspection
        assert cache_size() == 1, "mixed step must retrace O(1), not per shape"


# ------------------------------------------------------------------ #
# admission deadlock: typed error + graceful force-done
# ------------------------------------------------------------------ #
def test_resolve_fill_deps_orders_and_raises():
    from repro.serving.engine import AdmissionDeadlock, resolve_fill_deps

    # fills with satisfied deps run (slot order); blocked ones wait
    deps = {0: frozenset(), 1: frozenset({7}), 2: frozenset({5})}
    assert resolve_fill_deps(deps, {7}) == [0, 2]
    assert resolve_fill_deps({}, {7}) == []
    # every fill blocked -> typed error carrying the stuck slots
    with pytest.raises(AdmissionDeadlock) as ei:
        resolve_fill_deps({3: frozenset({20}), 4: frozenset({21})}, {20, 21})
    assert sorted(ei.value.stuck) == [3, 4]
    assert "stalled" in str(ei.value)


def test_admission_deadlock_force_dones_stuck_row(monkeypatch):
    """A stuck warm admission must retire with an EMPTY, deadlocked-
    flagged result (like OOM truncation: degrade, never wedge or
    corrupt), its pool blocks and cached-chunk registrations rolled back
    so later requests — including an identical resubmission — still
    serve exactly."""
    import repro.serving.engine as engine_mod
    from repro.serving.engine import AdmissionDeadlock

    eng = make_fake_engine(
        monkeypatch, max_batch=2, max_new_tokens=4, sched_chunk=2,
        paged=True, block_size=4, n_pool_blocks=8, prefix_cache=True,
    )
    real = engine_mod.resolve_fill_deps
    tripped = []

    def sabotage(fill_deps, pending):
        warm = [i for i, d in fill_deps.items() if d]
        if warm and not tripped:  # wedge only the first warm admission
            tripped.append(True)
            raise AdmissionDeadlock([], warm)
        return real(fill_deps, pending)

    monkeypatch.setattr(engine_mod, "resolve_fill_deps", sabotage)
    pre = np.full((4,), 7, np.int32)  # one full block -> shareable chunk
    prompts = [
        np.concatenate([pre, np.array([10], np.int32)]),  # cold
        np.concatenate([pre, np.array([20], np.int32)]),  # warm sibling: sabotaged
        np.concatenate([pre, np.array([20], np.int32)]),  # resubmission: must work
    ]
    sched = Scheduler()
    rids = sched.submit_many(prompts, 4)
    res = eng.serve(sched)
    assert list(res[rids[0]]) == expected_answer(10, 4)
    assert len(res[rids[1]]) == 0, "stuck admission must retire empty, not hang"
    assert sched.results[rids[1]].status == "done" and sched.results[rids[1]].deadlocked
    assert list(res[rids[2]]) == expected_answer(20, 4), "pool state corrupted by rollback"
    st = sched.latency_stats()
    assert st["n_deadlocked"] == 1 and st["n_truncated"] == 0


# ------------------------------------------------------------------ #
# resident engine: warm restart + tiered (spill) prefix cache
# ------------------------------------------------------------------ #
def test_warm_restart_reuses_resident_prefix_index(small_lm):
    """Acceptance: the prefix index + block pool survive across serve()
    calls on one engine — a second call over the same prompts is all
    hits (prefill tokens saved reported per window), stays bit-identical
    to a cold engine on the same admission order, and reset_cache()
    drops the residency for an explicit cold start."""
    cfg, params = small_lm
    mk = lambda: ServeConfig(max_batch=2, max_prompt_len=20, max_new_tokens=4,
                             sched_chunk=2, paged=True, prefix_cache=True,
                             block_size=4)
    rng = np.random.default_rng(7)
    pre = rng.integers(8, cfg.vocab_size, size=12).astype(np.int32)
    prompts = [
        np.concatenate([pre, rng.integers(8, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (2, 3)
    ]
    eng = ServeEngine(cfg, POL, params, mk())
    s1 = Scheduler()
    rids1 = s1.submit_many(prompts, 4)
    r1 = eng.serve(s1)
    st1 = s1.latency_stats()
    s2 = Scheduler()
    rids2 = s2.submit_many(prompts, 4)
    r2 = eng.serve(s2)
    st2 = s2.latency_stats()
    # call 2 rides the resident index: every prompt hits, prefill saved
    assert st1["prefix_hits"] == 1  # only the same-pass sibling hit cold
    assert st2["prefix_hits"] == len(prompts) and st2["prefix_hit_rate"] == 1.0
    assert st2["prefill_tokens_saved"] >= len(prompts) * len(pre)
    # warm results == cold engine on the same admission order, bit-exact
    cold = ServeEngine(cfg, POL, params, mk()).serve_prompts(prompts, max_new_tokens=4)
    for rid1, rid2, w in zip(rids1, rids2, cold):
        assert np.array_equal(r1[rid1], w)
        assert np.array_equal(r2[rid2], w), "warm restart changed tokens"
    # scheduler window covers ONE call; engine lifetime covers both
    assert st2["prefix_lookups"] == len(prompts)
    assert st2["lifetime"]["prefix_lookups"] == 2 * len(prompts)
    assert eng.prefix_lookups == 2 * len(prompts)
    # explicit cold start: residency dropped, hits gone
    eng.reset_cache()
    s3 = Scheduler()
    s3.submit_many(prompts, 4)
    eng.serve(s3)
    assert s3.latency_stats()["prefix_hits"] == 1


def test_spilled_chain_readmits_bit_identical(small_lm):
    """Acceptance: a cached chain demoted to the host tier under pool
    pressure re-admits by upload (not re-prefill) and decodes
    BIT-IDENTICALLY to its never-evicted first serve."""
    cfg, params = small_lm
    scfg = ServeConfig(max_batch=1, max_prompt_len=8, max_new_tokens=4,
                       sched_chunk=2, paged=True, prefix_cache=True,
                       block_size=4, n_pool_blocks=3, spill_bytes=4 << 20)
    rng = np.random.default_rng(9)
    a = rng.integers(8, cfg.vocab_size, size=8).astype(np.int32)
    b = rng.integers(8, cfg.vocab_size, size=8).astype(np.int32)
    eng = ServeEngine(cfg, POL, params, scfg)
    cold_a = eng.serve_prompts([a], max_new_tokens=4)[0]  # cold reference
    eng.serve_prompts([b], max_new_tokens=4)  # pool pressure demotes a's chain
    assert eng._index.n_demotions >= 1 and eng._index.n_spilled >= 1
    assert 0 <= eng._spill_store.used_bytes <= scfg.spill_bytes
    sched = Scheduler()
    rids = sched.submit_many([a], 4)
    warm_a = eng.serve(sched)[rids[0]]
    assert eng._index.n_readmits >= 1, "spilled chain must come back by upload"
    assert np.array_equal(warm_a, cold_a), "re-admitted chain changed tokens"
    st = sched.latency_stats()
    assert st["spill_readmits"] >= 1 and st["prefix_hits"] == 1
    assert st["lifetime"]["spill_demotions"] == eng._index.n_demotions


# ------------------------------------------------------------------ #
# per-tenant SLO classes through the engine
# ------------------------------------------------------------------ #
def test_tenant_priority_and_fifo_admission_order(monkeypatch):
    """Priority preempts the QUEUE (interactive requests submitted after
    a batch flood still admit first) while running slots always finish
    on their own terms; ``fifo=True`` restores global arrival order.
    Answers stay exact for every tenant and per-tenant stats surface."""
    from _fake_lm import VOCAB

    def run(fifo):
        eng = make_fake_engine(monkeypatch, max_batch=1, max_new_tokens=4, sched_chunk=2)
        sched = Scheduler(tenant_weights={"interactive": 4.0, "batch": 1.0}, fifo=fifo)
        b_rids = sched.submit_many(
            [prompt_ending(e) for e in (10, 20, 30)], 4, tenants="batch"
        )
        i_rids = sched.submit_many(
            [prompt_ending(e) for e in (40, 50)], 4, tenants="interactive", priorities=1
        )
        res = eng.serve(sched)
        for e, rid in zip((10, 20, 30), b_rids):
            assert list(res[rid]) == expected_answer(e, 4)
        for e, rid in zip((40, 50), i_rids):
            assert list(res[rid]) == expected_answer(e, 4)
        return sched, b_rids, i_rids

    sched, b_rids, i_rids = run(fifo=False)
    starts = {rid: sched.results[rid].started_at for rid in b_rids + i_rids}
    # the interactive class preempted the queue: both its requests
    # admitted before any batch request despite submitting last
    assert max(starts[r] for r in i_rids) < min(starts[r] for r in b_rids)
    # FIFO within each tenant never reorders
    assert starts[b_rids[0]] < starts[b_rids[1]] < starts[b_rids[2]]
    st = sched.latency_stats()
    assert st["tenants"]["interactive"]["n_done"] == 2
    assert st["tenants"]["batch"]["n_done"] == 3
    assert st["tenants"]["batch"]["n_admitted"] == 3
    assert st["tenants"]["interactive"]["tokens_out"] == 8
    assert "p95_s" in st["tenants"]["batch"]

    sched, b_rids, i_rids = run(fifo=True)
    starts = {rid: sched.results[rid].started_at for rid in b_rids + i_rids}
    # arrival-order baseline: the batch flood admits first
    assert max(starts[r] for r in b_rids) < min(starts[r] for r in i_rids)


# ------------------------------------------------------------------ #
# speculative decoding: draft-k/verify-1 through the unified dispatch
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_spec_decode_matches_plain_bitwise(small_lm, block_size):
    """Acceptance: draft-k/verify-1 speculation must be BIT-identical to
    plain greedy decode for the same admission order — self-speculation
    (drafter == target) on the ragged prompt/budget workload the paged
    parity suite uses, across block sizes, with verify rounds riding the
    same token-budget dispatch as chunked prefill lanes."""
    cfg, params = small_lm
    base_kw = dict(max_batch=2, max_prompt_len=11, max_new_tokens=5, sched_chunk=2)
    rng = np.random.default_rng(42)
    prompts = [
        rng.integers(8, cfg.vocab_size, size=n).astype(np.int32)
        for n in (9, 11, 6, 3, 11, 7)
    ]
    budgets = [5, 1, 4, 5, 2, 5]
    plain = ServeEngine(
        cfg, POL, params,
        ServeConfig(paged=True, block_size=block_size, token_budget=5, **base_kw),
    )
    want = plain.serve_prompts(prompts, max_new_tokens=budgets)
    spec = ServeEngine(
        cfg, POL, params,
        ServeConfig(paged=True, block_size=block_size, token_budget=5,
                    draft_k=3, **base_kw),
    )
    got = spec.serve_prompts(prompts, max_new_tokens=budgets)
    for i, (w, g) in enumerate(zip(want, got)):
        assert np.array_equal(w, g), f"prompt {i}: spec {list(g)} != plain {list(w)}"
    # self-speculation drafts the target's own tokens: accepts happen
    assert spec.spec_rounds > 0 and spec.spec_tokens_accepted > 0
    assert spec.decode_dispatches == 0, "spec rounds must ride the mixed dispatch"


def test_spec_decode_prefix_cache_matches_plain_bitwise(small_lm):
    """Speculation composes with the prefix cache: a COW + sibling
    workload (cold prompts, same-pass sibling, full-prefix hits) under
    draft-k must still match the plain contiguous oracle bit-for-bit
    while the cache actually shares."""
    cfg, params = small_lm
    base_kw = dict(max_batch=2, max_prompt_len=20, max_new_tokens=5, sched_chunk=2)
    rng = np.random.default_rng(42)
    pre = rng.integers(8, cfg.vocab_size, size=16).astype(np.int32)
    tails = [rng.integers(8, cfg.vocab_size, size=n).astype(np.int32) for n in (1, 3, 2)]
    prompts = [
        np.concatenate([pre, tails[0]]),
        np.concatenate([pre, tails[1]]),  # same-pass sibling
        pre.copy(),                        # full-prefix hit -> COW boundary
        rng.integers(8, cfg.vocab_size, size=9).astype(np.int32),
        pre.copy(),
        np.concatenate([pre, tails[2]]),
    ]
    budgets = [5, 1, 4, 5, 2, 3]
    oracle = ServeEngine(cfg, POL, params, ServeConfig(**base_kw))
    want = oracle.serve_prompts(prompts, max_new_tokens=budgets)
    spec = ServeEngine(
        cfg, POL, params,
        ServeConfig(paged=True, prefix_cache=True, block_size=8, token_budget=7,
                    draft_k=3, **base_kw),
    )
    got = spec.serve_prompts(prompts, max_new_tokens=budgets)
    for i, (w, g) in enumerate(zip(want, got)):
        assert np.array_equal(w, g), f"prompt {i}: spec {list(g)} != oracle {list(w)}"
    assert spec.prefix_hits >= 3 and spec.spec_tokens_accepted > 0


def test_spec_decode_divergent_drafter_never_changes_tokens(monkeypatch):
    """A drafter that ALWAYS disagrees (offset-2 rule vs the target's
    offset-1) must reject every draft — zero accepts — and the outputs
    still match plain decode exactly: correctness never depends on the
    drafter, only throughput does."""
    kw = dict(max_batch=3, max_new_tokens=6, sched_chunk=2,
              paged=True, block_size=4, token_budget=6)
    ends = [250, 0, 10, 253, 99, 30]
    budgets = [6, 3, 2, 6, 1, 4]
    eng = make_fake_engine(monkeypatch, draft_k=3, draft_params={"offset": 2}, **kw)
    sched = Scheduler()
    rids = sched.submit_many([prompt_ending(e) for e in ends], budgets)
    res = eng.serve(sched)
    for e, b, rid in zip(ends, budgets, rids):
        assert list(res[rid]) == expected_answer(e, b), f"end={e} budget={b}"
    assert eng.spec_tokens_proposed > 0 and eng.spec_tokens_accepted == 0
    st = sched.latency_stats()
    assert st["spec_accept_rate"] == 0.0
    # every lane still emits the target's lane-0 correction token
    assert st["spec_tokens_per_round"] >= 1.0


def test_spec_decode_dispatch_count_o2_per_round(monkeypatch):
    """CI guard: a speculative round costs at most TWO device dispatches
    — one drafter call (fill or k-token loop) + one target verify — with
    zero legacy decode dispatches, and self-speculation (perfect drafter)
    emits > 1 token per verify round."""
    eng = make_fake_engine(
        monkeypatch, max_batch=4, max_new_tokens=6, sched_chunk=2,
        paged=True, block_size=4, token_budget=8, draft_k=3,
    )
    ends = [250, 10, 99, 30, 200, 1]
    sched = Scheduler()
    rids = sched.submit_many([prompt_ending(e) for e in ends], 6)
    res = eng.serve(sched)
    for e, rid in zip(ends, rids):
        assert list(res[rid]) == expected_answer(e, 6)
    assert eng.decode_dispatches == 0 and eng.admit_dispatches == 0
    assert eng.spec_rounds > 0
    assert eng.draft_dispatches <= eng.spec_rounds, "O(2): <= 1 drafter call per round"
    st = sched.latency_stats()
    assert st["dispatches_per_spec_round"] <= 2.0
    assert st["spec_tokens_per_round"] > 1.0, "perfect drafter must beat 1 token/round"
    assert st["spec_accept_rate"] > 0.5
    assert st["engine_steps"] == st["mixed_dispatches"] + st["decode_dispatches"]
    # draft dispatches are overhead, not engine steps: the per-step gauge
    # still reads one TARGET dispatch per step
    assert st["dispatches_per_step"] == 1.0


def test_spec_decode_config_validation(small_lm, monkeypatch):
    cfg, params = small_lm
    with pytest.raises(ValueError, match="requires paged"):
        ServeEngine(cfg, POL, params, ServeConfig(draft_k=3))
    with pytest.raises(ValueError, match="must be >= 0"):
        ServeEngine(cfg, POL, params, ServeConfig(draft_k=-1, paged=True))
    with pytest.raises(ValueError, match="cannot fit one verify"):
        ServeEngine(
            cfg, POL, params,
            ServeConfig(draft_k=4, paged=True, token_budget=4, max_prompt_len=8),
        )
    with pytest.raises(ValueError, match="draft_config without draft_params"):
        ServeEngine(
            cfg, POL, params,
            ServeConfig(draft_k=3, paged=True, draft_config=cfg, max_prompt_len=8),
        )
    small = cfg.with_overrides(vocab_size=cfg.vocab_size // 2)
    with pytest.raises(ValueError, match="vocab_size"):
        ServeEngine(
            cfg, POL, params,
            ServeConfig(draft_k=3, paged=True, draft_config=small, draft_params={},
                        max_prompt_len=8),
        )


@pytest.mark.parametrize("option", ["contiguous", "shards", "draft_k"])
def test_latent_attention_refuses_the_paths_it_has_no_cache_for(option):
    """Latent attention with held experts serves on the paged engine only:
    the contiguous engine, the sharded pool and the speculative drafter
    have no latent path, so each is refused at construction, never run
    wrong."""
    from repro.configs.base import ModelConfig
    from repro.models.params import init_params

    cfg = ModelConfig(
        name="tiny-mla", family="moe", n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
        d_ff=64, vocab_size=64, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, n_experts=8, moe_top_k=2, moe_d_ff=16, n_shared_experts=1,
        experts_held=4, first_dense=1, dtype="float32")
    params = init_params(LM.param_specs(cfg), jax.random.PRNGKey(0))
    kw = {"contiguous": dict(paged=False), "shards": dict(paged=True, shards=1),
          "draft_k": dict(paged=True, draft_k=2)}[option]
    with pytest.raises(ValueError, match="tiny-mla"):
        ServeEngine(cfg, POL, params, ServeConfig(max_batch=2, max_prompt_len=16,
                                                  max_new_tokens=4, block_size=4, **kw))
