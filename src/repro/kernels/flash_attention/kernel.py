"""Flash attention (prefill) Pallas kernel — causal + GQA.

Grid (B*H, Sq/BQ, Sk/BK), KV innermost (arbitrary).  Running (m, l, acc)
live in VMEM scratch, revisited across the KV sweep; the final normalized
block is written once on the last KV step.  GQA is handled in the k/v
index_map (query head h reads KV head h // group) so KV blocks are shared
across the group without materializing repeats in HBM.

Block defaults 256/512 keep q(BQ,dh)+k/v(BK,dh)+p(BQ,BK) comfortably in
VMEM for dh<=128 while giving the MXU 128-aligned contractions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_backend

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, causal, bq, bk, scale, n_k):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # whole block is masked out iff q_block_end < k_block_start
        run = (qi + 1) * bq - 1 >= kj * bk

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (BQ, dh)
        k = k_ref[0].astype(jnp.float32)  # (BK, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, KV, dh)
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 512,
):
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    group = h // kv
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    scale = 1.0 / np.sqrt(dh)

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, dh)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, dh)

    grid = (b * h, sq // bq, sk // bk)

    def kv_map(bh, qi, kj):
        return (bh // h) * kv + (bh % h) // group, kj, 0

    def build(interpret):
        return pl.pallas_call(
            functools.partial(
                _kernel, causal=causal, bq=bq, bk=bk, scale=scale, n_k=sk // bk
            ),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, dh), lambda bh, qi, kj: (bh, qi, 0)),
                pl.BlockSpec((1, bk, dh), kv_map),
                pl.BlockSpec((1, bk, dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, bq, dh), lambda bh, qi, kj: (bh, qi, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, sq, dh), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, dh), jnp.float32),
            ],
            interpret=interpret,
        )

    out = on_backend(build)(qt, kt, vt)
    return out.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
