"""Plain reference of the Qwen3 dense decoder (Qwen3ForCausalLM): a
teacher-forced forward in float32 at the highest matmul precision, layer
by layer, with no kernels, cache or batching tricks.

Per layer: RMSNorm, q/k/v projections, RMSNorm of each q and k head
(qk-norm), rotary embedding on the two halves of each head, causal
grouped-query softmax attention (query head j reads key/value head
j // (heads / kv_heads)), output projection, residual; RMSNorm, SwiGLU
MLP, residual.  Then a final RMSNorm and the LM head (the embedding
transposed when the embeddings are tied).  Weights come from
``bench.lib.weights`` and are read layer by layer, cast to float32.

``quantize`` turns this into the control: the operands of every
projection and of the LM head are rounded to a lower precision first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * inv  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _identity(x):
    return x


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quantize"))
def layer_forward(h, w, *, eps, theta, quantize=_identity):
    """h: (B, S, d) float32 residual stream; w: one layer's leaves."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)

    def mm(spec, a, b):
        return jnp.einsum(spec, quantize(a), quantize(b), precision=HI)

    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _rms(h, w["attn_norm"], eps)
    q = _rms(mm("bsd,dhk->bshk", x, w["wq"]), w["q_norm"], eps)
    k = _rms(mm("bsd,dhk->bshk", x, w["wk"]), w["k_norm"], eps)
    v = mm("bsd,dhk->bshk", x, w["wv"])
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / np.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HI)
    h = h + mm("bqhk,hkd->bqd", o, w["wo"])
    x = _rms(h, w["ffn_norm"], eps)
    a = jax.nn.silu(mm("bsd,df->bsf", x, w["wg"])) * mm("bsd,df->bsf", x, w["wu"])
    return h + mm("bsf,fd->bsd", a, w["wd"])


@functools.partial(jax.jit, static_argnames=("eps", "quantize"))
def head_logits(h, final_norm, head, *, eps, quantize=_identity):
    """h: (N, d) -> (N, V) float32 logits; head: (d, V)."""
    x = _rms(h, final_norm.astype(jnp.float32), eps)
    return jnp.einsum("nd,dv->nv", quantize(x), quantize(head.astype(jnp.float32)), precision=HI)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


def hidden_states(model: dict, weights: dict, seqs: list[np.ndarray], quantize=_identity,
                  block_bytes: float = 1.0e9) -> tuple[jax.Array, int]:
    """Residual stream after the last layer for each sequence, padded at
    the end to a common length (a multiple of 512).  Sequences run in
    blocks sized so that one block's attention scores stay under
    ``block_bytes``.  Returns ((n, S, d) float32, S)."""
    n_heads, eps = model["num_attention_heads"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])
    s_pad = -(-max(len(x) for x in seqs) // 512) * 512
    blk = max(1, int(block_bytes // (n_heads * s_pad * s_pad * 4)))
    n_pad = -(-len(seqs) // blk) * blk
    tokens = np.zeros((n_pad, s_pad), np.int32)
    for i, x in enumerate(seqs):
        tokens[i, : len(x)] = x
    h = _embed(weights["embed"], jnp.asarray(tokens))
    layers = weights["layers"]
    for i in range(model["num_hidden_layers"]):
        w = jax.tree.map(lambda a: a[i], layers)
        h = jnp.concatenate([
            layer_forward(h[j : j + blk], w, eps=eps, theta=theta, quantize=quantize)
            for j in range(0, n_pad, blk)
        ])
    return h[: len(seqs)], s_pad


@functools.partial(jax.jit, static_argnames=("eps", "quantize"))
def _gaps(h_ref, h_ctl, final_norm, head, served, *, eps, quantize):
    """Per position: how far the reference logit of the served token, and
    of the token the control puts first, lie below the reference's best,
    in standard deviations of the reference's logits at that position
    (the unit keeps one limit valid at any width)."""
    ref = head_logits(h_ref, final_norm, head, eps=eps)
    best, sd = ref.max(-1), ref.std(-1)
    served_gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    pick = jnp.argmax(head_logits(h_ctl, final_norm, head, eps=eps, quantize=quantize), -1)
    return served_gap / sd, (best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]) / sd


def logit_gaps(model: dict, weights: dict, prompts: list[np.ndarray], answers: list[np.ndarray],
               quantize=None, block: int = 256) -> tuple[np.ndarray, np.ndarray | None]:
    """Teacher-forced over each prompt with its served answer.  Returns,
    for every served token, its gap below the reference's best logit at
    that position (in standard deviations of that position's logits); and, given ``quantize``, the gap of the token that the
    control (the reference in that lower precision) puts first there."""
    seqs = [np.concatenate([p, a[:-1]]).astype(np.int32) for p, a in zip(prompts, answers)]
    h, _ = hidden_states(model, weights, seqs)
    hq = None if quantize is None else hidden_states(model, weights, seqs, quantize)[0]
    rows, cols, served = [], [], []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        rows += [i] * len(a)
        cols += list(range(len(p) - 1, len(p) - 1 + len(a)))
        served += [int(t) for t in a]
    rows, cols, served = (np.asarray(x, np.int32) for x in (rows, cols, served))
    head = weights["embed"].T if model["tie_word_embeddings"] else weights["head"]
    eps = model["rms_norm_eps"]
    out_s, out_c = [], []
    for j in range(0, len(rows), block):
        n = len(rows[j : j + block])
        r, c, t = (np.pad(x[j : j + block], (0, block - n)) for x in (rows, cols, served))
        h_ctl = h[r, c] if hq is None else hq[r, c]
        g_s, g_c = _gaps(h[r, c], h_ctl, weights["final_norm"], head, jnp.asarray(t), eps=eps,
                         quantize=_identity if quantize is None else quantize)
        out_s.append(np.asarray(g_s)[:n])
        out_c.append(np.asarray(g_c)[:n])
    return np.concatenate(out_s), None if quantize is None else np.concatenate(out_c)
