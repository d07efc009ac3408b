"""Plain reference of the DeepSeek-V3 decoder (``model_type: deepseek_v3``,
as Moonlight-16B-A3B publishes it): a teacher-forced forward in float32
under ``jax.default_matmul_precision("highest")`` (and ``HIGHEST`` on
every product), layer by layer, with no kernels, cache
or batching tricks.  It imports nothing of the program.

Per layer: RMSNorm; latent attention in its published, non-absorbed form
(no query LoRA): q = x W_q, split per head into 128 "nope" and 64 rope
lanes; [c, k_pe] = x W_kv_a, c RMS-normed; [k_nope, v] = c W_kv_b per
head; rope on q_pe and on the one shared k_pe; causal softmax over
[q_nope, q_pe] . [k_nope, k_pe] at scale 1/sqrt(192); o = p v, then
W_o; residual.  RMSNorm, then the FFN: a SwiGLU MLP in the leading
dense layers; elsewhere the MoE layer: sigmoid scores of the router over
all its experts in float32, the top k picked by score plus the
correction bias, weighted by the unbiased scores, normalised (plus
1e-20) and scaled by ``routed_scaling_factor``; the shared experts as
one un-gated SwiGLU MLP; residual.  Final RMSNorm, untied LM head.

Departures from the published modelling code, each deliberate:

* the expert share: the configuration's ``deployment`` says which
  experts this chip holds (``experts_held``), and only they contribute,
  as in the program (the absent experts' part would come from other
  chips); the router keeps its published width (``router_width``);
* rope's pair layout: the published code takes the rope lanes as
  interleaved pairs and de-interleaves them before rotating halves; here
  the lanes are in half layout from the start.  On seeded random weights
  that is the same model with the rope columns of W_q and W_kv_a
  permuted;
* ``rope_scaling`` is null, so the softmax scale has no yarn factor;
* with ``n_group`` and ``topk_group`` 1 the group-limited selection
  selects the one group and changes nothing, so it is left out.

``quantize`` turns this into the control: the operands of every
projection, of the experts and of the LM head are rounded to a lower
precision first.
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
    """x: (B, S, heads, hd) in half layout; pos: (B, S)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _identity(x):
    return x


def _mlp(mm, x, wg, wu, wd):
    return mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", x, wg)) * mm("bsd,df->bsf", x, wu), wd)


def attention(h, w, m: dict, mm):
    """One layer's attention block on the residual stream h (B, S, d)."""
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    nope, rope, r = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["kv_lora_rank"]
    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _rms(h, w["attn_norm"], eps)
    q = mm("bsd,dhk->bshk", x, w["wq"])
    kv = mm("bsd,dc->bsc", x, w["wkv_a"])
    c, k_pe = _rms(kv[..., :r], w["kv_norm"], eps), kv[..., r:]
    kvb = mm("bsc,chk->bshk", c, w["wkv_b"])
    heads = q.shape[2]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], -1)
    k_pe = jnp.broadcast_to(_rope(k_pe[:, :, None, :], pos, theta), (b, s, heads, rope))
    k = jnp.concatenate([kvb[..., :nope], k_pe], -1)
    v = kvb[..., nope:]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) * (nope + rope) ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HI)
    return h + mm("bqhk,hkd->bqd", o, w["wo"])


def route(x, w, m: dict):
    """(weights (B, S, k), expert ids (B, S, k)) of the published router."""
    logits = jnp.einsum("bsd,de->bse", x, w["router"], precision=HI)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + w["select_bias"], m["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, ids, -1)
    if m["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * m["routed_scaling_factor"], ids


def moe(x, w, m: dict, mm):
    """The MoE FFN of normed input x (B, S, d): the held experts' part of
    the routed sum, plus the shared experts."""
    weights, ids = route(x, w, m)
    first = m["deployment"]["experts_held"][0]
    out = _mlp(mm, x, w["shared_wg"], w["shared_wu"], w["shared_wd"])
    for e in range(w["wg"].shape[0]):
        gate = jnp.where(ids == first + e, weights, 0.0).sum(-1)[..., None]
        out = out + gate * _mlp(mm, x, w["wg"][e], w["wu"][e], w["wd"][e])
    return out


@functools.partial(jax.jit, static_argnames=("kind", "mkey", "quantize"))
def layer_forward(h, w, *, kind, mkey, quantize=_identity):
    """h: (B, S, d) float32 residual stream; w: one layer's leaves; kind:
    "dense" or "moe"; mkey: the configuration as a hashable tuple."""
    m = _unkey(mkey)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)

    def mm(spec, a, b):
        return jnp.einsum(spec, quantize(a), quantize(b), precision=HI)

    h = attention(h, w, m, mm)
    x = _rms(h, w["ffn_norm"], m["rms_norm_eps"])
    if kind == "dense":
        return h + _mlp(mm, x, w["wg"], w["wu"], w["wd"])
    return h + moe(x, w, m, mm)


def _key(m: dict) -> tuple:
    keys = ("rms_norm_eps", "rope_theta", "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")
    return tuple((k, m[k]) for k in keys) + (("first_held", m["deployment"]["experts_held"][0]),)


def _unkey(mkey: tuple) -> dict:
    m = dict(mkey)
    m["deployment"] = {"experts_held": [m.pop("first_held")]}
    return m


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
    n_heads = model["num_attention_heads"]
    s_pad = -(-max(len(x) for x in seqs) // 512) * 512
    blk = max(1, int(block_bytes // (n_heads * s_pad * s_pad * 4)))
    n_pad = -(-len(seqs) // blk) * blk
    tokens = np.zeros((n_pad, s_pad), np.int32)
    for i, x in enumerate(seqs):
        tokens[i, : len(x)] = x
    h = _embed(weights["embed"], jnp.asarray(tokens))
    mkey = _key(model)
    for kind in ("dense", "moe"):
        group = weights[kind]
        for i in range(jax.tree.leaves(group)[0].shape[0]):
            w = jax.tree.map(lambda a: a[i], group)
            h = jnp.concatenate([
                layer_forward(h[j : j + blk], w, kind=kind, mkey=mkey, quantize=quantize)
                for j in range(0, n_pad, blk)
            ])
    return h[: len(seqs)], s_pad


def forward_logits(model: dict, weights: dict, tokens: np.ndarray) -> jax.Array:
    """Full forward of one sequence: (S,) tokens -> (S, V) float32 logits."""
    with jax.default_matmul_precision("highest"):
        h, _ = hidden_states(model, weights, [np.asarray(tokens, np.int32)])
        return head_logits(h[0, : len(tokens)], weights["final_norm"], weights["head"],
                           eps=model["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("eps", "quantize"))
def _gaps(h_ref, h_ctl, final_norm, head, served, *, eps, quantize):
    """Per position: how far the reference logit of the served token, and
    of the token the control puts first, lie below the reference's best,
    in standard deviations of the reference's logits at that position."""
    ref = head_logits(h_ref, final_norm, head, eps=eps)
    best, sd = ref.max(-1), ref.std(-1)
    served_gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    pick = jnp.argmax(head_logits(h_ctl, final_norm, head, eps=eps, quantize=quantize), -1)
    return served_gap / sd, (best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]) / sd


def logit_gaps(model: dict, weights: dict, prompts: list[np.ndarray], answers: list[np.ndarray],
               quantize=None, block: int = 256) -> tuple[np.ndarray, np.ndarray | None]:
    """Teacher-forced over each prompt with its served answer.  Returns,
    for every served token, its gap below the reference's best logit at
    that position (in standard deviations of that position's logits);
    and, given ``quantize``, the gap of the token that the control (the
    reference in that lower precision) puts first there."""
    seqs = [np.concatenate([p, a[:-1]]).astype(np.int32) for p, a in zip(prompts, answers)]
    with jax.default_matmul_precision("highest"):
        h, _ = hidden_states(model, weights, seqs)
        hq = None if quantize is None else hidden_states(model, weights, seqs, quantize)[0]
    rows, cols, served = [], [], []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        rows += [i] * len(a)
        cols += list(range(len(p) - 1, len(p) - 1 + len(a)))
        served += [int(t) for t in a]
    rows, cols, served = (np.asarray(x, np.int32) for x in (rows, cols, served))
    eps = model["rms_norm_eps"]
    out_s, out_c = [], []
    for j in range(0, len(rows), block):
        n = len(rows[j : j + block])
        r, c, t = (np.pad(x[j : j + block], (0, block - n)) for x in (rows, cols, served))
        h_ctl = h[r, c] if hq is None else hq[r, c]
        with jax.default_matmul_precision("highest"):
            g_s, g_c = _gaps(h[r, c], h_ctl, weights["final_norm"], weights["head"], jnp.asarray(t),
                             eps=eps, quantize=_identity if quantize is None else quantize)
        out_s.append(np.asarray(g_s)[:n])
        out_c.append(np.asarray(g_c)[:n])
    return np.concatenate(out_s), None if quantize is None else np.concatenate(out_c)
