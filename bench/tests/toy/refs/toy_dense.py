"""Plain reference of the toy architecture ``toy_dense``: the Qwen3
reference (``bench/refs/qwen3.py``), read from a private copy of that
module, with a layer that has no qk-norm in place of its own."""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import spec

_BASE = spec.load_module(os.path.join(spec.BENCH_DIR, "refs", "qwen3.py"), "bench_ref_toy_dense_base")
HI, _rms, _rope = _BASE.HI, _BASE._rms, _BASE._rope


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quantize"))
def layer_forward(h, w, *, eps, theta, quantize=_BASE._identity):
    """h: (B, S, d) float32 residual stream; w: one layer's leaves."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)

    def mm(spec, a, b):
        return jnp.einsum(spec, quantize(a), quantize(b), precision=HI)

    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _rms(h, w["attn_norm"], eps)
    q, k, v = (mm("bsd,dhk->bshk", x, w[n]) for n in ("wq", "wk", "wv"))
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


_BASE.layer_forward = layer_forward  # the copy's hidden_states runs this layer
logit_gaps = _BASE.logit_gaps
