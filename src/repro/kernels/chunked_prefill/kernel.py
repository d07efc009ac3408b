"""Unified chunked-prefill Pallas kernel: paged flash attention over a
ragged q-tile, one dispatch for any mix of prefill chunks and decode steps.

Same scalar-prefetch split as ``paged_decode_attention_pallas`` — the
block table never materializes a gather in HBM; the BlockSpec index map
reads the prefetched table to DMA pool block ``tbl[desc[r, 0], t]`` per
grid step — but the q block is a (W, H) *tile of lanes* instead of a
single token, with per-row descriptors ``(slot, q_start, q_len, kv_len)``
carrying the ragged geometry (see ref.py for the mask contract).  Cold
prefills, warm suffix prefills riding a shared prefix, and 1-token decode
rows (q_len == 1) all run in the same grid.

Grid (R, KV, n_t); all W lanes x G group heads of a (row, kv-head) pair
ride in one (W*G, BS) logits block so the MXU sees a real tile even when
most rows are decodes.  Where that block would not fit VMEM (absorbed
MLA: G = 16 query heads over one 576-wide latent head) the lanes are cut
into query tiles on a grid axis of their own, ``(R, KV, n_tiles, n_t)``.  Each row walks only its live blocks ``0 ..
cdiv(kv_len, BS) - 1`` (none when ``q_len == 0``): the K/V index maps
clamp to the last live block, so later steps copy nothing, and the body
skips them, so the kernel's time follows the live KV, not the table.
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


# VMEM a (row, kv-head) query block may take with its output and
# accumulator; a larger block is cut into query tiles (a grid axis)
VMEM_BUDGET = 8 << 20


def _q_tile_lanes(w: int, g: int, dh: int, dv: int, q_bytes: int) -> int:
    """Lanes per query tile: all ``w`` when the whole ``(W*G, dh)`` block
    (double-buffered), its double-buffered f32 output and its accumulator
    fit ``VMEM_BUDGET``; else ``w`` halved until the tile does."""
    def nbytes(lanes):
        return lanes * g * (2 * dh * q_bytes + 3 * dv * 4)

    lanes = w
    while nbytes(lanes) > VMEM_BUDGET and lanes % 2 == 0 and lanes > 1:
        lanes //= 2
    return lanes


def _mixed_kernel(desc_ref, tbl_ref, q_ref, k_ref, *refs, bs, scale, n_t, g, tl, dv, tiled):
    """Online softmax over pool blocks for one (row, kv-head) pair and
    one query tile of ``tl`` lanes (the grid has a tile axis only when a
    row's lanes are cut into several).

    The flattened q axis interleaves lanes and group heads as
    ``i = lane * g + group``, so ``lane = i // g`` recovers the logical
    query position offset.  Probabilities are re-zeroed under the mask
    after the exp: for a live lane that's an exact identity (masked
    logits are NEG_INF, exp(NEG_INF - m) == +0.0 whenever any position
    is live), but a fully-masked lane keeps m == NEG_INF so the exp
    would give exp(0) == 1 per position — zeroing makes dead lanes
    contribute l == 0 and output exactly 0 instead.

    Blocks at or past ``kv_len``, every block of a ``q_len == 0`` row,
    and every block of a tile whose lanes are all past ``q_len`` are
    skipped: there each lane's update is the identity (``p == 0``,
    ``alpha == 1``), and the clamped index maps never copy the block the
    table names, so its contents are never read.

    ``dv``: None when V has a pool of its own (``refs`` starts with its
    block); else V is the first ``dv`` lanes of the K block, read once
    (the latent head of absorbed MLA)."""
    if dv is None:
        v_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    ri = pl.program_id(0)
    qi = pl.program_id(2) if tiled else 0
    tj = pl.program_id(3 if tiled else 2)

    @pl.when(tj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((tj * bs < desc_ref[ri, 3]) & (desc_ref[ri, 2] > qi * tl))
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)  # (tl*G, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (BS, dh)
        v = v_ref[0, 0].astype(jnp.float32) if dv is None else k[:, :dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (tl*G, BS)
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        if tiled:
            lane = qi * tl + lane
        kpos = tj * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = desc_ref[ri, 1] + lane
        valid = (kpos <= qpos) & (kpos < desc_ref[ri, 3]) & (lane < desc_ref[ri, 2])
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
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


def mixed_prefill_attention_pallas(
    q: jax.Array,  # (R, W, H, dh) — W ragged query lanes per row
    k_pool: jax.Array,  # (n_pool, bs, KV, dh) shared block pool
    v_pool: jax.Array | None,  # (n_pool, bs, KV, dh), or None: V is K's first v_dim lanes
    block_tables: jax.Array,  # (B, n_t) int32 pool ids per cache slot
    desc: jax.Array,  # (R, 4) int32 (slot, q_start, q_len, kv_len)
    *,
    scale: float | None = None,  # None -> 1/sqrt(dh)
    v_dim: int | None = None,  # with v_pool None: V's lanes of the K head
):
    """Paged flash attention for a mixed prefill+decode batch: descriptors
    plus the block table ride scalar prefetch; K/V stream from the pool
    block by block (no HBM gather) while every lane masks causally within
    its own ``(q_start + lane, kv_len)`` span.

    With ``v_pool`` None (absorbed latent attention: one KV head whose
    first ``v_dim`` lanes are the values) each grid step reads the block
    once for both, and the output has ``v_dim`` lanes.  Where a row's
    ``(W*G, dh)`` query block would not fit ``VMEM_BUDGET`` the grid
    gains a query-tile axis ``(R, KV, n_tiles, n_t)``; otherwise it is
    ``(R, KV, n_t)``."""
    r, w, h, dh = q.shape
    n_pool, bs, kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    n_t = block_tables.shape[1]
    g = h // kv
    scale = 1.0 / np.sqrt(dh) if scale is None else scale
    shared = v_pool is None
    dv = v_dim if shared else dh
    tl = _q_tile_lanes(w, g, dh, dv, q.dtype.itemsize)
    n_qt = w // tl
    tiled = n_qt > 1

    # (R, W, KV, G, dh) -> (R, KV, W*G, dh): lanes x groups flatten so one
    # block per (row, kv-head) covers the whole ragged tile
    qg = q.reshape(r, w, kv, g, dh).transpose(0, 2, 1, 3, 4).reshape(r, kv, w * g, dh)
    if kv == 1:  # moving a unit axis: a reshape, no copy of the pool
        kt = k_pool.reshape(n_pool, 1, bs, dh)
    else:
        kt = k_pool.transpose(0, 2, 1, 3)  # (n_pool, KV, BS, dh)
    operands = [qg, kt]
    if not shared:
        operands.append(v_pool.transpose(0, 2, 1, 3))
    grid = (r, kv, n_qt, n_t) if tiled else (r, kv, n_t)

    def ids(idx):
        return idx if tiled else (idx[0], idx[1], 0, idx[2])

    def row_map(*a):
        ri, ki, qi, _ = ids(a[:-2])
        return ri, ki, qi, 0

    def kv_map(*a):
        # past a row's last live block the index stops changing, so the
        # pipeline issues no further copy; a q_len == 0 row sits on block 0,
        # and a tile with no live lane on the row's last live block
        (ri, ki, qi, tj), dsc, tbl = ids(a[:-2]), a[-2], a[-1]
        last = jnp.where(dsc[ri, 2] > 0, jnp.maximum((dsc[ri, 3] + bs - 1) // bs - 1, 0), 0)
        if not tiled:  # one tile: the map runs every grid step, so no select it does not need
            return tbl[dsc[ri, 0], jnp.minimum(tj, last)], ki, 0, 0
        t = jnp.where(dsc[ri, 2] > qi * tl, jnp.minimum(tj, last), last)
        return tbl[dsc[ri, 0], t], ki, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # desc, block_tables
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tl * g, dh), row_map),
        ] + [pl.BlockSpec((1, 1, bs, dh), kv_map)] * (len(operands) - 1),
        out_specs=[
            pl.BlockSpec((1, 1, tl * g, dv), row_map),
            pl.BlockSpec((1, 1, tl * g, 1), row_map),
            pl.BlockSpec((1, 1, tl * g, 1), row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((tl * g, 1), jnp.float32),
            pltpu.VMEM((tl * g, 1), jnp.float32),
            pltpu.VMEM((tl * g, dv), jnp.float32),
        ],
    )
    def build(interpret):
        return pl.pallas_call(
            functools.partial(_mixed_kernel, bs=bs, scale=scale, n_t=n_t, g=g, tl=tl,
                              dv=dv if shared else None, tiled=tiled),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((r, kv, w * g, dv), jnp.float32),
                jax.ShapeDtypeStruct((r, kv, w * g, 1), jnp.float32),
                jax.ShapeDtypeStruct((r, kv, w * g, 1), jnp.float32),
            ],
            interpret=interpret,
        )

    o, m, l = on_backend(build)(
        desc.astype(jnp.int32), block_tables.astype(jnp.int32), *operands
    )
    out = o / jnp.maximum(l, 1e-30)
    out = out.reshape(r, kv, w, g, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(r, w, h, dv).astype(q.dtype)
