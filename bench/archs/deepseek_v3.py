"""DeepSeek-V3 decoder (``model_type: deepseek_v3``, as Moonlight-16B-A3B
publishes it): what the benchmark knows of it.

Per layer: RMSNorm and latent attention (MLA, no query LoRA: heads of 128
"nope" and 64 rope lanes scored against one cached latent of 512 lanes
plus a shared 64-lane rope key; the values and keys are per-head
up-projections of the latent); RMSNorm and an FFN, a SwiGLU MLP in the
``first_k_dense_replace`` leading layers (group ``dense``) and in every
later layer (group ``moe``) a sigmoid-routed mixture of experts with a
selection bias and un-gated shared experts.  Then a final RMSNorm and the
untied LM head.  The configuration's ``deployment`` gives the chip's share
of an expert-parallel deployment: the router keeps its published width
(``router_width``) and the chip holds and computes ``experts_held`` (the
top-level ``n_routed_experts`` counts them).  The plain reference is
``bench/refs/deepseek_v3.py``.
"""
from __future__ import annotations

import numpy as np

# the CPU tests' cut (bench/tests/tiny.py): three layers of width 64, one
# dense, then two with experts; the router keeps the deployment's width
TINY = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, vocab_size=8192)

BIAS_STD = 0.05  # the seeded e_score_correction_bias: nonzero, so the bias moves picks


def _held(m: dict) -> list[int]:
    held = list(m["deployment"]["experts_held"])
    if len(held) != m["n_routed_experts"] or held != list(range(held[0], held[0] + len(held))):
        raise ValueError(f"experts_held {held} must be n_routed_experts={m['n_routed_experts']} "
                         "consecutive experts")
    return held


# --- weights (bench/lib/weights.py): shape and init standard deviation of
# each leaf, None for a norm scale

def global_leaves(m: dict) -> dict:
    d, v = m["hidden_size"], m["vocab_size"]
    return {"embed": ((v, d), 0.02), "final_norm": ((d,), None), "head": ((d, v), d ** -0.5)}


def _attn_leaves(m: dict) -> dict:
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return {
        "attn_norm": ((d,), None),
        "wq": ((d, h, nope + rope), d ** -0.5),
        "wkv_a": ((d, r + rope), d ** -0.5),
        "kv_norm": ((r,), None),
        "wkv_b": ((r, h, nope + dv), r ** -0.5),
        "wo": ((h, dv, d), (h * dv) ** -0.5),
        "ffn_norm": ((d,), None),
    }


def layer_groups(m: dict) -> dict:
    """``dense``: the leading dense layers; ``moe``: the rest."""
    d, f, fe = m["hidden_size"], m["intermediate_size"], m["moe_intermediate_size"]
    e, fs = len(_held(m)), m["n_shared_experts"] * fe
    n_dense = m["first_k_dense_replace"]
    dense = dict(_attn_leaves(m), wg=((d, f), d ** -0.5), wu=((d, f), d ** -0.5),
                 wd=((f, d), f ** -0.5))
    moe = dict(_attn_leaves(m),
               router=((d, m["deployment"]["router_width"]), d ** -0.5),
               select_bias=((m["deployment"]["router_width"],), BIAS_STD),
               wg=((e, d, fe), d ** -0.5), wu=((e, d, fe), d ** -0.5), wd=((e, fe, d), fe ** -0.5),
               shared_wg=((d, fs), d ** -0.5), shared_wu=((d, fs), d ** -0.5),
               shared_wd=((fs, d), fs ** -0.5))
    return {"dense": (n_dense, dense), "moe": (m["num_hidden_layers"] - n_dense, moe)}


# --- the program's side

def model_config(m: dict):
    """The program's ``ModelConfig``; a program without latent attention
    and held experts stops here, naming what it lacks."""
    import dataclasses

    from repro.configs.base import ModelConfig

    need = {"kv_lora_rank", "experts_held", "expert_offset", "moe_scale", "first_dense"}
    missing = sorted(need - {f.name for f in dataclasses.fields(ModelConfig)})
    if missing:
        raise NotImplementedError(
            f"this program cannot serve {m['name']}: its ModelConfig has no {', '.join(missing)} "
            "(latent attention, held experts with DeepSeek-V3 routing, leading dense layers)")
    if m["q_lora_rank"] is not None or m["n_group"] != 1 or m["topk_group"] != 1 \
            or m["moe_layer_freq"] != 1 or m["topk_method"] != "noaux_tc" \
            or m["scoring_func"] != "sigmoid" or not m["norm_topk_prob"]:
        raise ValueError("deepseek_v3 is bound for no query LoRA, one routing group, an expert "
                         "layer after every leading dense layer, and noaux_tc selection of "
                         "sigmoid scores, normalised")
    s, held = m["serving"], _held(m)
    return ModelConfig(
        name=m["name"], family="moe",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=1, d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]), tie_embeddings=bool(m["tie_word_embeddings"]),
        kv_lora_rank=m["kv_lora_rank"], qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        n_experts=m["deployment"]["router_width"], moe_top_k=m["num_experts_per_tok"],
        moe_d_ff=m["moe_intermediate_size"], n_shared_experts=m["n_shared_experts"],
        experts_held=len(held), expert_offset=held[0],
        moe_scale=float(m["routed_scaling_factor"]), first_dense=m["first_k_dense_replace"],
        attn_impl=s["attn_impl"], dtype=m["torch_dtype"], param_dtype=m["torch_dtype"],
        logit_dtype="float32",
    )


def program_params(w: dict, m: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (no copy)."""
    def attn(l):
        return {k: l[k] for k in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")}

    d, e = w["dense"], w["moe"]
    lead = {"pos0": {"mixer_norm": d["attn_norm"], "attn": attn(d), "ffn_norm": d["ffn_norm"],
                     "mlp": {k: d[k] for k in ("wg", "wu", "wd")}}}
    moe = {k: e[k] for k in ("router", "select_bias", "wg", "wu", "wd")}
    moe["shared"] = {k: e["shared_" + k] for k in ("wg", "wu", "wd")}
    blocks = {"pos0": {"mixer_norm": e["attn_norm"], "attn": attn(e), "ffn_norm": e["ffn_norm"],
                       "moe": moe}}
    return {"embed": {"tok": w["embed"]}, "lead": lead, "blocks": blocks,
            "final_norm": w["final_norm"], "head": {"w": w["head"]}}


# --- work counts (bench/lib/derive.py)

KV_BYTES = 2  # bytes per cached latent element (bf16 pool)
ACT_BYTES = 2  # bytes per query element fed to attention (bf16)
OUT_BYTES = 4  # bytes per attention output element (the kernels write f32)


def routing(run) -> dict:
    """The routing counters (``moe_pairs_held``, ``moe_experts_touched``,
    ``moe_max_expert_pairs``) summed over the run's ``engine.dispatch``
    spans; all 0 where the program records none."""
    from bench.lib import spans

    names = ("moe_pairs_held", "moe_experts_touched", "moe_max_expert_pairs")
    recs = spans.log() if run is not None else None
    disp = [r for r in recs or [] if r.name == "engine.dispatch"]
    return {k: sum(int(r.attrs.get(k, 0)) for r in disp) for k in names}


def _widths(m: dict):
    h, r, rope = m["num_attention_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    return h, r + rope, r  # heads, latent head width (K), value lanes


def token_flops(m: dict) -> float:
    """FLOPs of one token through every layer's projections and every
    FFN but the routed experts (those follow the routing), absorbed MLA:
    q, kv_a, the nope query folded into the latent (W_uk), the latent
    values up-projected (W_uv), o."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, r, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["kv_lora_rank"], m["v_head_dim"]
    attn = d * h * (nope + rope) + d * (r + rope) + h * nope * r + h * r * dv + h * dv * d
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    dense = 3 * d * m["intermediate_size"]
    moe = 3 * d * m["n_shared_experts"] * m["moe_intermediate_size"] + d * m["deployment"]["router_width"]
    return 2.0 * (m["num_hidden_layers"] * attn + n_dense * dense + n_moe * moe)


def expert_flops(m: dict, pairs: float) -> float:
    """FLOPs of ``pairs`` (token, held expert) pairs: three matmuls of
    d x f, 2 per MAC."""
    return 6.0 * m["hidden_size"] * m["moe_intermediate_size"] * pairs


def attn_flops(m: dict, ctx) -> float:
    """Scores over the 576-lane latent head and values over its 512 value
    lanes, all heads and layers, for tokens attending to ``ctx`` keys."""
    h, k, v = _widths(m)
    return 2.0 * m["num_hidden_layers"] * h * (k + v) * float(np.sum(ctx))


def attn_bytes(m: dict, kv_len, n_q) -> float:
    """Least bytes an attention kernel moves, all layers: the latent of
    each row's ``kv_len`` keys read once (it is both key and value), its
    ``n_q`` queries read and outputs written once."""
    h, k, v = _widths(m)
    kv = k * KV_BYTES * float(np.sum(kv_len))
    q = h * (k * ACT_BYTES + v * OUT_BYTES) * float(np.sum(n_q))
    return m["num_hidden_layers"] * (kv + q)


def step_flops(m: dict, live, run=None) -> float:
    """Useful FLOPs of the live work: every live token through the layer
    stack, the held experts' part by the pairs the run's dispatches
    routed to them, the LM head where logits are used, attention over
    each token's context."""
    return (live.tokens * token_flops(m) + expert_flops(m, routing(run)["moe_pairs_held"])
            + live.head_tokens * 2.0 * m["hidden_size"] * m["vocab_size"]
            + attn_flops(m, live.prefill_ctx) + attn_flops(m, live.decode_ctx))


def attn_work(m: dict, live, which: str, run=None) -> tuple[float, float]:
    """(FLOPs, least bytes) of one attention kernel's live work: ``which``
    is "prefill" (the chunked-prefill kernel of the mixed step) or
    "decode" (the paged decode kernel of the fused decode chunk)."""
    if which == "prefill":
        return attn_flops(m, live.prefill_ctx), attn_bytes(m, live.prefill_kv, live.prefill_q)
    return attn_flops(m, live.decode_ctx), attn_bytes(m, live.decode_ctx, live.decode_q)


def expert_ops(m: dict) -> str:
    """A pattern that the device trace's name of an operation matches when
    the operation reads the held experts' weights: the expert matmuls,
    whose HLO text carries a weight operand of shape ``[E, d, f]`` or
    ``[E, f, d]`` (E the experts held; the layers' stacked ``[L, E, ...]``
    where the slice is fused in)."""
    e, d, f = len(_held(m)), m["hidden_size"], m["moe_intermediate_size"]
    return rf"\[(\d+,)?{e},({d},{f}|{f},{d})\]"


def expert_work(m: dict, run) -> tuple[float, float]:
    """(FLOPs, least bytes) of the held experts' live work in the run's
    dispatches: 6 d f FLOPs a (token, held expert) pair; the touched
    experts' three weight matrices read once per layer and step that
    touched them, and each pair's activations read and written once."""
    r = routing(run)
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    nbytes = r["moe_experts_touched"] * 3 * d * f * KV_BYTES + r["moe_pairs_held"] * 2 * d * ACT_BYTES
    return expert_flops(m, r["moe_pairs_held"]), float(nbytes)
