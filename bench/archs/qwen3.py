"""Qwen3 dense decoder (``model_type: qwen3``, Qwen3ForCausalLM): what the
benchmark knows of it.

Per layer: RMSNorm, q/k/v projections in grouped-query heads, RMSNorm of
each q and k head (qk-norm), rotary embedding, causal attention, output
projection; RMSNorm and a SwiGLU MLP.  Then a final RMSNorm and the LM
head, the embedding transposed when the embeddings are tied.  The plain
reference is ``bench/refs/qwen3.py``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# the CPU tests' cut (bench/tests/tiny.py): two layers of width 64
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=128, num_hidden_layers=2, vocab_size=8192)


# --- weights (bench/lib/weights.py): shape and init standard deviation of
# each leaf, None for a norm scale

def global_leaves(m: dict) -> dict:
    d, v = m["hidden_size"], m["vocab_size"]
    out = {"embed": ((v, d), 0.02), "final_norm": ((d,), None)}
    if not m["tie_word_embeddings"]:
        out["head"] = ((d, v), d ** -0.5)
    return out


def layer_groups(m: dict) -> dict:
    """One group, ``layers``: every layer alike."""
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    hd, f = m["head_dim"], m["intermediate_size"]
    return {"layers": (m["num_hidden_layers"], {
        "attn_norm": ((d,), None),
        "wq": ((d, h, hd), d ** -0.5),
        "wk": ((d, kv, hd), d ** -0.5),
        "wv": ((d, kv, hd), d ** -0.5),
        "wo": ((h, hd, d), (h * hd) ** -0.5),
        "q_norm": ((hd,), None),
        "k_norm": ((hd,), None),
        "ffn_norm": ((d,), None),
        "wg": ((d, f), d ** -0.5),
        "wu": ((d, f), d ** -0.5),
        "wd": ((f, d), f ** -0.5),
    })}


# --- the program's side

def model_config(m: dict):
    """The program's ``ModelConfig``."""
    from repro.configs.base import ModelConfig

    s = m["serving"]
    return ModelConfig(
        name=m["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        qk_norm=True, rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        attn_impl=s["attn_impl"], dtype=m["torch_dtype"], param_dtype=m["torch_dtype"],
        logit_dtype="float32",
    )


def program_params(w: dict, m: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (no copy)."""
    l = w["layers"]
    blocks = {"pos0": {
        "mixer_norm": l["attn_norm"],
        "attn": {k: l[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")},
        "ffn_norm": l["ffn_norm"],
        "mlp": {k: l[k] for k in ("wg", "wu", "wd")},
    }}
    p = {"embed": {"tok": w["embed"]}, "blocks": blocks, "final_norm": w["final_norm"]}
    if not m["tie_word_embeddings"]:
        p["head"] = {"w": w["head"]}
    return p


# --- work counts (bench/lib/derive.py): operations and bytes of the live
# geometry that bench/lib/work.py sums; the work does not depend on the run

@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2  # bytes per cached key or value element (bf16 pool)
    act_bytes: int = 2  # bytes per query element fed to attention (bf16)
    out_bytes: int = 4  # bytes per attention output element (the kernels write f32)

    @classmethod
    def of(cls, m: dict) -> "Shape":
        return cls(m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"], m["intermediate_size"], m["vocab_size"])

    @property
    def matmul_flops_per_token(self) -> float:
        """Projections and MLP of all layers for one token (2 per MAC)."""
        qkv = self.d * (self.heads + 2 * self.kv_heads) * self.head_dim
        o = self.heads * self.head_dim * self.d
        mlp = 3 * self.d * self.d_ff
        return 2.0 * self.layers * (qkv + o + mlp)

    @property
    def head_flops(self) -> float:
        return 2.0 * self.d * self.vocab

    def attn_flops(self, ctx) -> float:
        """Scores and weighted values, all layers, for tokens attending to
        ``ctx`` keys each (an array or a number)."""
        return 4.0 * self.layers * self.heads * self.head_dim * float(np.sum(ctx))

    def attn_bytes(self, kv_len, n_q) -> float:
        """Least bytes an attention kernel moves, all layers: every key and
        value of each row's ``kv_len`` read once, its ``n_q`` queries read
        and outputs written once."""
        kv = 2.0 * self.kv_heads * self.head_dim * self.kv_bytes * float(np.sum(kv_len))
        q = self.heads * self.head_dim * (self.act_bytes + self.out_bytes) * float(np.sum(n_q))
        return self.layers * (kv + q)


def step_flops(m: dict, live, run=None) -> float:
    """Useful FLOPs of the live work: every live token through the layer
    stack, the LM head where logits are used, attention over each token's
    context."""
    s = Shape.of(m)
    return (live.tokens * s.matmul_flops_per_token + live.head_tokens * s.head_flops
            + s.attn_flops(live.prefill_ctx) + s.attn_flops(live.decode_ctx))


def attn_work(m: dict, live, which: str, run=None) -> tuple[float, float]:
    """(FLOPs, least bytes) of one attention kernel's live work: ``which``
    is "prefill" (the chunked-prefill kernel of the mixed step) or
    "decode" (the paged decode kernel of the fused decode chunk)."""
    s = Shape.of(m)
    if which == "prefill":
        return s.attn_flops(live.prefill_ctx), s.attn_bytes(live.prefill_kv, live.prefill_q)
    return s.attn_flops(live.decode_ctx), s.attn_bytes(live.decode_ctx, live.decode_q)
