"""Flash-decode Pallas kernel: single-token attention against a long KV
cache, with (m, l, o) partials exposed for cross-device combine.

decode_32k / long_500k are memory-bound (read the whole KV cache once per
token); the kernel streams the cache through VMEM in BS-length tiles and
keeps the softmax state on-chip.  ``return_partials=True`` yields per-call
(m, l, o) so serving/dist_decode.py can shard the cache seq-dim over the
`data` axis and combine partials with one tiny psum — the beyond-paper
long-context optimization in EXPERIMENTS.md §Perf.

Grid (B, KV, S/BS); all H/KV query heads of a group ride in one block so
the (G, BS) logits hit the MXU.
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


def _kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *, bs, scale, n_s):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (BS, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, BS)
    kpos = sj * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < len_ref[0], s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(sj == n_s - 1)
    def _finish():
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def decode_attention_pallas(
    q: jax.Array,  # (B, H, dh) — one new token per sequence
    k_cache: jax.Array,  # (B, S, KV, dh)
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,) valid cache length per sequence
    *,
    bs: int = 512,
    return_partials: bool = False,
):
    b, h, dh = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    bs = min(bs, s)
    assert s % bs == 0, (s, bs)
    scale = 1.0 / np.sqrt(dh)

    qg = q.reshape(b, kv, g, dh)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, KV, S, dh)
    vt = v_cache.transpose(0, 2, 1, 3)
    grid = (b, kv, s // bs)

    def build(interpret):
        return pl.pallas_call(
            functools.partial(_kernel, bs=bs, scale=scale, n_s=s // bs),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, dh), lambda bi, ki, sj: (bi, ki, 0, 0)),
                pl.BlockSpec((1, 1, bs, dh), lambda bi, ki, sj: (bi, ki, sj, 0)),
                pl.BlockSpec((1, 1, bs, dh), lambda bi, ki, sj: (bi, ki, sj, 0)),
                pl.BlockSpec((1,), lambda bi, ki, sj: (bi,)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, g, dh), lambda bi, ki, sj: (bi, ki, 0, 0)),
                pl.BlockSpec((1, 1, g, 1), lambda bi, ki, sj: (bi, ki, 0, 0)),
                pl.BlockSpec((1, 1, g, 1), lambda bi, ki, sj: (bi, ki, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, kv, g, dh), jnp.float32),
                jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
                jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ],
            interpret=interpret,
        )

    o, m, l = on_backend(build)(qg, kt, vt, lengths)
    if return_partials:
        return o, m, l  # caller combines across shards then normalizes
    out = o / jnp.maximum(l, 1e-30)
    return out.reshape(b, h, dh).astype(q.dtype)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, *refs, bs, scale, n_t, dv):
    """Grid (B, KV, n_max_blocks).  The scalar-prefetched block table
    drives the K/V BlockSpec index maps, so pool block ``tbl[b, t]``
    streams into VMEM for (batch b, logical block t) — the gather never
    materializes in HBM.  Masking is positional: logical position
    ``t * bs + lane`` is valid iff < lengths[b] — trash-backed lanes are
    always past the length and contribute exp(-inf) = 0 exactly.

    ``dv``: None when V has a pool of its own (``refs`` starts with its
    block); else V is the first ``dv`` lanes of the K block, read once
    (the latent head of absorbed MLA)."""
    if dv is None:
        v_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    tj = pl.program_id(2)
    bi = pl.program_id(0)

    @pl.when(tj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)  # (BS, dh)
    v = v_ref[0, 0].astype(jnp.float32) if dv is None else k[:, :dv]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, BS)
    kpos = tj * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < len_ref[bi], s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * alpha + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(tj == n_t - 1)
    def _finish():
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def paged_decode_attention_pallas(
    q: jax.Array,  # (B, H, dh) — one new token per sequence
    k_pool: jax.Array,  # (n_pool, bs, KV, dh) shared block pool
    v_pool: jax.Array | None,  # (n_pool, bs, KV, dh), or None: V is K's first v_dim lanes
    block_tables: jax.Array,  # (B, n_max_blocks) int32 pool ids per row
    lengths: jax.Array,  # (B,) valid cache length per sequence
    *,
    scale: float | None = None,  # None -> 1/sqrt(dh)
    v_dim: int | None = None,  # with v_pool None: V's lanes of the K head
):
    """Flash-decode over a PAGED KV cache: same online-softmax stream as
    ``decode_attention_pallas``, but the sequence axis is a block table —
    the BlockSpec index map reads the scalar-prefetched table to pick
    which pool block to DMA per grid step (the vLLM-style paged-attention
    gather, done by the memory system instead of an HBM materialize).

    With ``v_pool`` None (absorbed latent attention: one KV head whose
    first ``v_dim`` lanes are the values) each grid step reads the block
    once for both, and the output has ``v_dim`` lanes."""
    b, h, dh = q.shape
    n_pool, bs, kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    n_t = block_tables.shape[1]
    g = h // kv
    scale = 1.0 / np.sqrt(dh) if scale is None else scale
    shared = v_pool is None
    dv = v_dim if shared else dh

    qg = q.reshape(b, kv, g, dh)
    if kv == 1:  # moving a unit axis: a reshape, no copy of the pool
        kt = k_pool.reshape(n_pool, 1, bs, dh)
    else:
        kt = k_pool.transpose(0, 2, 1, 3)  # (n_pool, KV, BS, dh)
    kv_spec = pl.BlockSpec((1, 1, bs, dh), lambda bi, ki, tj, tbl, lens: (tbl[bi, tj], ki, 0, 0))
    operands = [qg, kt]
    if not shared:
        operands.append(v_pool.transpose(0, 2, 1, 3))
    grid = (b, kv, n_t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda bi, ki, tj, tbl, lens: (bi, ki, 0, 0)),
        ] + [kv_spec] * (len(operands) - 1),
        out_specs=[
            pl.BlockSpec((1, 1, g, dv), lambda bi, ki, tj, tbl, lens: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bi, ki, tj, tbl, lens: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bi, ki, tj, tbl, lens: (bi, ki, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    def build(interpret):
        return pl.pallas_call(
            functools.partial(_paged_kernel, bs=bs, scale=scale, n_t=n_t,
                              dv=dv if shared else None),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, kv, g, dv), jnp.float32),
                jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
                jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
            ],
            interpret=interpret,
        )

    o, m, l = on_backend(build)(
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands
    )
    out = o / jnp.maximum(l, 1e-30)
    return out.reshape(b, h, dv).astype(q.dtype)


def combine_partials(o, m, l):
    """Combine a list of (o, m, l) partials from disjoint cache shards."""
    m_g = jnp.max(jnp.stack(m), axis=0)
    scaled_l = [li * jnp.exp(mi - m_g) for mi, li in zip(m, l)]
    scaled_o = [oi * jnp.exp(mi - m_g) for mi, oi in zip(m, o)]
    l_g = sum(scaled_l)
    return sum(scaled_o) / jnp.maximum(l_g, 1e-30)
