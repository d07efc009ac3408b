"""Operations and bytes that a dense decoder's serving step needs, counted
from shapes and from the live geometry of each dispatch.

Only live work counts: prompt tokens actually prefilled, tokens actually
decoded, each with the keys it attends to (its position plus one), and
the LM head only where its logits are used (a decode token, and the last
prompt token of a finished fill).  Padded lanes, idle rows and rows that
are already done never count, so what any implementation of a layer
computes on top of this is waste, and a share of a peak or a roofline
built on these counts cannot pass 100%.
"""
from __future__ import annotations

import dataclasses

import numpy as np


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


@dataclasses.dataclass
class Live:
    """Live work of a set of dispatches, split by the kernel that ran it."""

    tokens: int = 0  # tokens through the layer stack
    head_tokens: int = 0  # tokens whose logits were used: the answer tokens emitted
    prefill_ctx: float = 0.0  # keys attended, summed over chunked-prefill-kernel lanes
    prefill_kv: float = 0.0  # keys read, summed over that kernel's rows
    prefill_q: int = 0
    decode_ctx: float = 0.0  # the same for the paged decode kernel
    decode_q: int = 0

    def add(self, other: "Live") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))

    def flops(self, s: Shape) -> float:
        return (self.tokens * s.matmul_flops_per_token + self.head_tokens * s.head_flops
                + s.attn_flops(self.prefill_ctx) + s.attn_flops(self.decode_ctx))


def mixed_live(live: Live, q_start, q_len, is_decode, done, lengths, emitted, row_len) -> None:
    """Add one unified mixed step (all arrays per row, read back after the
    window).  A decode row is live when it has a lane and is not done; its
    one token sits at ``lengths + emitted - 1``.  A fill row streams
    ``q_len`` prompt tokens from ``q_start``; the step that reaches
    ``row_len`` uses its last lane's logits."""
    for r in range(len(q_len)):
        n = int(q_len[r])
        if n <= 0:
            continue
        if is_decode[r]:
            if done[r]:
                continue
            start, n, head = int(lengths[r] + emitted[r] - 1), 1, 1
        else:
            start = int(q_start[r])
            head = int(start + n >= row_len[r])
        ctx = np.arange(start + 1, start + n + 1)
        live.tokens += n
        live.head_tokens += head
        live.prefill_ctx += float(ctx.sum())
        live.prefill_kv += start + n
        live.prefill_q += n


def decode_live(live: Live, lengths, emitted_in, emitted_out, done) -> None:
    """Add one fused decode chunk: a row that was not done emitted
    ``emitted_out - emitted_in`` tokens, the j-th at position
    ``lengths + emitted_in - 1 + j``, attending to that position plus one
    keys."""
    for r in range(len(lengths)):
        k = int(emitted_out[r] - emitted_in[r])
        if done[r] or k <= 0:
            continue
        ctx = np.arange(k) + int(lengths[r] + emitted_in[r])
        live.tokens += k
        live.head_tokens += k
        live.decode_ctx += float(ctx.sum())
        live.decode_q += k


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for the work, over the time it took;
    and which bound sets that least time."""
    t_f, t_b = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"]
    return max(t_f, t_b) / seconds, ("flops" if t_f >= t_b else "bytes")
