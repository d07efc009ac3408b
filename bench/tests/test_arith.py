"""The arithmetic of the metrics: percentiles with missing queries, the
answer-token count, FLOPs per token, and roofline shares that cannot pass
100%."""
import math

import numpy as np
import pytest

from bench.lib import spec, stats, work
from bench.lib.record import Query, Run, StepRecorder

QWEN3 = spec.arch({"model_type": "qwen3"})

QWEN3_06B = {"num_hidden_layers": 28, "hidden_size": 1024, "num_attention_heads": 16,
             "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 3072,
             "vocab_size": 151936}


def test_percentile_counts_missing_queries_as_slowest():
    served = [100.0, 200.0, 300.0, 400.0, 500.0, 600.0, 700.0, 800.0]
    assert stats.percentile(served, 50) == 400.0
    # two of ten never answered: the 90th percentile is one of them
    assert stats.percentile(served, 90, n_missing=2) == math.inf
    assert stats.percentile(served, 90, n_missing=2, missing_value=9e9) == 9e9
    assert stats.percentile(served, 50, n_missing=2) == 500.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_query_percentile_reads_unserved_queries_as_the_wait():
    from bench.lib.derive import query_percentile

    run = Run(seconds=10.0, traced=False, t0=0.0, t1=10.0, t_end=70.0)
    run.queries = [Query(i, "q", 4, due=float(i), answered=float(i) + 1.0, status="done") for i in range(9)]
    run.queries.append(Query(9, "q", 4, due=9.0, status="failed"))
    run.queries.append(Query(10, "q", 4, due=11.0, answered=12.0, status="done"))  # due after the window
    assert query_percentile(run, 50) == 1000.0
    assert query_percentile(run, 100) == (70.0 - 9.0) * 1e3


def test_flops_per_token_of_qwen3_06b_match_a_hand_count():
    s = QWEN3.Shape.of(QWEN3_06B)
    per_layer = 1024 * 128 * (16 + 8 + 8) + 16 * 128 * 1024 + 3 * 1024 * 3072  # 15.73M params
    assert s.matmul_flops_per_token == 2 * 28 * per_layer
    assert abs(s.matmul_flops_per_token - 0.881e9) < 0.001e9
    assert s.head_flops == 2 * 1024 * 151936  # 0.311 GFLOP where logits are used
    assert s.attn_flops(1000) == 4 * 28 * 16 * 128 * 1000
    # one decoded token whose logits are used, attending to 1000 keys
    one = work.Live(tokens=1, head_tokens=1, decode_ctx=1000.0, decode_q=1)
    assert QWEN3.step_flops(QWEN3_06B, one) == 2 * 28 * per_layer + 2 * 1024 * 151936 + 4 * 28 * 16 * 128 * 1000


class _FakeEngine:
    """The engine's two step programs and dispatch counters, without a
    device: a decode chunk emits 3 tokens per row."""

    def __init__(self):
        self.mixed_dispatches = self.decode_dispatches = 0
        self._mixed_rows = lambda *a: ("mixed",) + a
        self._decode_chunk = lambda *a: (None, None, a[4] + 3, None, None)

    def step(self, kind: str, *a):
        out = (self._mixed_rows if kind == "mixed" else self._decode_chunk)(*a)
        if kind == "mixed":
            self.mixed_dispatches += 1
        else:
            self.decode_dispatches += 1
        return out


def test_answer_tokens_count_only_live_lanes_of_dispatches_finished_in_the_window(monkeypatch):
    # each dispatch is called at one tick and ends at the next: [1, 2], [3, 4], [5, 6]
    clock = iter([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    monkeypatch.setattr("bench.lib.record.time.monotonic", lambda: next(clock))
    eng = _FakeEngine()
    rec = StepRecorder(eng)
    i = lambda *v: np.asarray(v)  # noqa: E731
    # a mixed step: row 0 decodes (live), row 1 decodes but is done, row 2
    # fills 100 tokens from 0 of a 100-token prompt (its first token), row 3
    # fills 50 of 200 (no token), row 4 idle
    eng.step("mixed", None, None, None, i(10, 20, 0, 0, 0), i(3, 4, 0, 0, 0), i(0, 1, 1, 1, 1), None, None,
             None, i(0, 0, 0, 0, 0), i(1, 1, 100, 50, 0), i(1, 1, 0, 0, 0), i(0, 0, 100, 200, 0),
             None, None)
    # a decode chunk: rows 0 and 2 emit 3 tokens each, row 1 is done
    eng.step("decode", None, None, None, i(10, 20, 100), i(4, 4, 1), i(0, 1, 0), None, None, None, None)
    eng.step("decode", None, None, None, i(10, 20, 100), i(7, 4, 4), i(0, 1, 0), None, None, None, None)
    rec.close()
    assert rec.live().head_tokens == 2 + 3 + 3 + 3 + 3
    assert rec.answer_tokens(0.0, 10.0) == 2 + 6 + 6
    assert rec.answer_tokens(2.5, 10.0) == 6 + 6
    # a dispatch cut by an edge counts in the share of its span inside
    assert rec.answer_tokens(0.0, 5.25) == 2 + 6 + 6 * 0.25
    assert rec.answer_tokens(1.5, 3.5) == 2 * 0.5 + 6 * 0.5
    assert rec.answer_tokens(2.0, 3.0) == 0
    live = work.Live()
    (_, _, first), *_ = rec.dispatches()
    live.add(first)
    assert live.tokens == 1 + 100 + 50 and live.prefill_q == 151
    # the decode token sits at 10 + 3 - 1 = 12 and attends to 13 keys
    assert live.prefill_ctx == 13 + sum(range(1, 101)) + sum(range(1, 51))
    assert live.prefill_kv == 13 + 100 + 50


def test_step_recorder_refuses_dispatches_it_did_not_see():
    eng = _FakeEngine()
    rec = StepRecorder(eng)
    eng.mixed_dispatches += 1  # a step program the recorder does not wrap
    with pytest.raises(RuntimeError, match="does not wrap"):
        rec.close()


def _padded_mixed_kernel_work(s, b, w, n_t, bs):
    """What the chunked-prefill kernel computes and reads per layer call:
    every row, kv head and table block, whatever is live."""
    g = s.heads // s.kv_heads
    flops = b * s.kv_heads * n_t * 4.0 * (w * g) * bs * s.head_dim
    nbytes = b * s.kv_heads * n_t * 2.0 * bs * s.head_dim * s.kv_bytes + b * w * s.heads * s.head_dim * (
        s.act_bytes + s.out_bytes)
    return flops * s.layers, nbytes * s.layers


def test_roofline_share_of_live_work_cannot_pass_100_percent():
    s = QWEN3.Shape.of(QWEN3_06B)
    peak = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    b, w, bs, n_t = 8, 256, 16, 160
    rng = np.random.default_rng(0)
    for _ in range(200):
        live = work.Live()
        q_len = rng.integers(0, w + 1, b)
        q_start = rng.integers(0, n_t * bs - w, b)
        is_dec = rng.random(b) < 0.3
        work.mixed_live(live, q_start, q_len, is_dec, rng.random(b) < 0.2,
                        rng.integers(1, 2000, b), rng.integers(1, 400, b), q_start + q_len + rng.integers(0, 2, b))
        f_pad, b_pad = _padded_mixed_kernel_work(s, b, w, n_t, bs)
        f_live, b_live = QWEN3.attn_work(QWEN3_06B, live, "prefill")
        assert f_live <= f_pad and b_live <= b_pad
        # a kernel that did its padded work at the chip's peaks
        fastest = max(f_pad / peak["bf16_flops"], b_pad / peak["hbm_bytes_s"])
        share, bound = work.roofline_share(f_live, b_live, fastest, peak)
        assert 0.0 <= share <= 1.0 and bound in ("flops", "bytes")
