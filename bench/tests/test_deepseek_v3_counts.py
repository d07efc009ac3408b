"""The work counts of the ``deepseek_v3`` architecture and the readers of
its two metrics: hand counts at Moonlight-16B-A3B's published widths, the
routing read from the program's ``engine.dispatch`` spans, and the held
experts' roofline share found by operation names in a trace."""
import json
import os

import numpy as np
import pytest

from bench.lib import spec, work
from bench.lib.record import Run
from bench.lib.trace import Reduction

CONF = os.path.join(spec.BENCH_DIR, "configs", "moonlight-16b-a3b-fed4.json")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


@pytest.fixture(scope="module")
def moon():
    with open(CONF) as f:
        m = json.load(f)
    return m, spec.arch(m)


class _Rec:
    def __init__(self, name, attrs):
        self.name, self.attrs = name, attrs


def test_counts_match_a_hand_count_at_published_widths(moon):
    m, arch = moon
    attn = 2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128 + 16 * 128 * 2048
    dense, shared = 3 * 2048 * 11264, 3 * 2048 * 2816 + 2048 * 64
    assert arch.token_flops(m) == 2 * (27 * attn + dense + 26 * shared)
    assert abs(attn - 13.76e6) < 0.01e6  # about 13.8 M attention parameters a layer
    assert arch.expert_flops(m, 10) == 10 * 6 * 2048 * 1408
    # per key: 16 heads x (576 + 512) MACs; the latent read once, 1,152 B a token a layer
    assert arch.attn_flops(m, 1) == 27 * 2 * 16 * (576 + 512)
    assert arch.attn_bytes(m, 1, 0) == 27 * 1152
    assert arch.attn_bytes(m, 0, 1) == 27 * 16 * (576 * 2 + 512 * 4)


def test_routing_and_step_flops_read_the_dispatch_spans(moon, monkeypatch):
    from bench.lib import spans

    m, arch = moon
    recs = [_Rec("engine.dispatch", {"moe_pairs_held": 30, "moe_experts_touched": 12,
                                     "moe_max_expert_pairs": 5}),
            _Rec("engine.dispatch", {"moe_pairs_held": 7, "moe_experts_touched": 4,
                                     "moe_max_expert_pairs": 3}),
            _Rec("engine.step", {"moe_pairs_held": 1000})]
    monkeypatch.setattr(spans, "log", lambda: recs)
    run = Run(seconds=1.0, traced=True)
    assert arch.routing(run) == {"moe_pairs_held": 37, "moe_experts_touched": 16,
                                 "moe_max_expert_pairs": 8}
    live = work.Live(tokens=2, head_tokens=1, decode_ctx=100.0, decode_q=1)
    assert arch.step_flops(m, live, run) == (2 * arch.token_flops(m) + arch.expert_flops(m, 37)
                                             + 2 * 2048 * 163840 + arch.attn_flops(m, 100.0))
    flops, nbytes = arch.expert_work(m, run)
    assert flops == 37 * 6 * 2048 * 1408
    assert nbytes == 16 * 3 * 2048 * 1408 * 2 + 37 * 2 * 2048 * 2
    monkeypatch.setattr(spans, "log", lambda: None)
    assert arch.routing(run)["moe_pairs_held"] == 0


def _reduction(names, modules):
    n = len(names)
    ops = np.asarray([(10.0 * i, 10.0 * i + 4.0) for i in range(n)])
    return Reduction(ops=ops, op_names=names, op_module=np.asarray(modules),
                     modules=np.asarray([(0.0, 1e9), (0.0, 1e9)]),
                     module_names=["jit_decode_chunk(123)", "jit_cow_copy(9)"],
                     spans=[], offset_ns=0.0, window=(0.0, 1e9), n_devices=1)


def test_the_expert_roofline_reads_only_the_ops_on_held_expert_weights(moon, monkeypatch):
    from bench.lib import spans

    m, arch = moon
    cell = type("C", (), {"arch": arch, "model": m})()
    names = [
        "%fusion.387 = bf16[8,1408,16]{1,2,0} fusion(bf16[26,8,2048,1408]{3,2,1,0} %g.1, s32[] %i)",
        "%fusion.388 = bf16[16,2048]{1,0} fusion(f32[16,8]{0,1} %s, bf16[26,8,1408,2048]{3,2,1,0} %g.2)",
        "%fusion.389 = bf16[16,2816]{1,0} fusion(bf16[26,2048,2816]{2,1,0} %g.3)",  # shared expert
        '%c = f32[16,1,16,512] custom-call(bf16[8,2048,1408] %x), custom_call_target="tpu_custom_call"',
        "%fusion.9 = bf16[8,1408,16]{1,2,0} fusion(bf16[26,8,2048,1408]{3,2,1,0} %g.1)",  # another program
    ]
    run = Run(seconds=1.0, traced=True)
    run.trace = _reduction(names, [0, 0, 0, 0, 1])
    run.extra["peak"] = PEAK
    monkeypatch.setattr(spans, "log", lambda: [_Rec("engine.dispatch", {
        "moe_pairs_held": 96, "moe_experts_touched": 40, "moe_max_expert_pairs": 6})])
    reader = spec.load_module(os.path.join(spec.BENCH_DIR, "metrics", "expert_ffn_roofline.py"),
                              "expert_ffn_roofline_test")
    t = 8e-9  # the two expert fusions, 4 ns each
    flops, nbytes = arch.expert_work(m, run)
    want = 100.0 * max(flops / PEAK["bf16_flops"], nbytes / PEAK["hbm_bytes_s"]) / t
    assert reader.value(run, cell) == pytest.approx(want)
    run.trace = _reduction(names[2:4], [0, 0])
    assert reader.value(run, cell) is None
