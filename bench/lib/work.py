"""The live geometry of the engine's dispatches, summed, from which the
configuration's architecture module (``bench/archs/<model_type>.py``)
counts operations and bytes; and a roofline share from such counts.

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
