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


# K bytes (the V tile is as large) one tile of live blocks streams: enough
# that a tile's copies keep the DMA engines busy while the previous tile
# computes, small enough that both buffers of K and V and the tile's f32
# working copies stay far inside the scoped VMEM
TILE_BYTES = 256 << 10


def _tile_blocks(n_t: int, block_bytes: int) -> int:
    """Live blocks per tile: the largest power of two whose K tile stays
    within ``TILE_BYTES`` (at least one block), and no more than the
    table holds."""
    t = 1
    while 2 * t * block_bytes <= TILE_BYTES and t < n_t:
        t *= 2
    return min(t, n_t)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_hbm, *refs, bs, scale, tile, dv):
    """Grid (B,): one step per row, all KV heads at once.  The pool stays
    in HBM; a ``fori_loop`` walks the row's live blocks ``0 ..
    cdiv(lengths[b], bs) - 1`` in tiles of ``tile`` blocks, each block one
    DMA of its whole ``(KV, bs, lanes)`` slab (pool block ``tbl[b, j]``)
    into a double-buffered VMEM tile, the next tile's copies started
    before the current tile computes.  No block past the live ones is
    copied, and a row of length 0 copies nothing and keeps ``acc = 0,
    l = 0, m = NEG_INF``.  Masking is positional (``kpos < lengths[b]``):
    the slots of a partial last tile hold blocks an earlier tile or row
    copied, or the zeros the buffers start with (the grid runs in order,
    so the first step clears them once), and contribute ``p == 0`` times
    a finite value.

    ``lanes`` (the buffers' last axis) is ``dh``, or on the chip ``dh``
    rounded up to whole 128-lane tiles: the pool's HBM layout pads a
    576-lane head to 640, and a DMA slice must cover whole tiles.  The
    pad lanes are copied and never read.

    ``dv``: None when V has a pool of its own (``refs`` starts with it);
    else V is the first ``dv`` lanes of the K block, read once (the
    latent head of absorbed MLA)."""
    if dv is None:
        v_hbm, o_ref, m_ref, l_ref, k_buf, v_buf, sem = refs
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf))
    else:
        o_ref, m_ref, l_ref, k_buf, sem = refs
        pairs = ((k_hbm, k_buf),)
    bi = pl.program_id(0)
    n_t = tbl_ref.shape[1]
    kv, dh = q_ref.shape[1], q_ref.shape[3]
    lanes = k_buf.shape[-1]
    length = len_ref[bi]
    n_live = jnp.minimum((length + bs - 1) // bs, n_t)
    n_tiles = (n_live + tile - 1) // tile

    @pl.when(bi == 0)
    def _():
        for _, buf in pairs:
            buf[...] = jnp.zeros_like(buf)

    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def tile_copies(i, slot, start):
        """Start (or wait for) the DMA of each live block of tile ``i``."""
        for u in range(tile):
            j = i * tile + u

            @pl.when(j < n_live)
            def _():
                blk = tbl_ref[bi, j]
                for n, (src, dst) in enumerate(pairs):
                    copy = pltpu.make_async_copy(
                        src.at[blk, :, :, pl.ds(0, lanes)], dst.at[slot, :, u], sem.at[n, slot])
                    if start:
                        copy.start()
                    else:
                        copy.wait()

    @pl.when(n_tiles > 0)
    def _():
        tile_copies(0, 0, True)

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_tiles)
        def _():
            tile_copies(i + 1, 1 - slot, True)

        tile_copies(i, slot, False)
        kpos = i * tile * bs + jax.lax.broadcasted_iota(jnp.int32, (1, tile * bs), 1)
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)  # (G, dh)
            k = k_buf[slot, h].reshape(tile * bs, lanes)[:, :dh].astype(jnp.float32)
            if dv is None:
                v = v_buf[slot, h].reshape(tile * bs, lanes)[:, :dh].astype(jnp.float32)
            else:
                v = k[:, :dv]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (G, T*bs)
            s = jnp.where(kpos < length, s, NEG_INF)
            m_prev, l_prev = m_ref[0, h], l_ref[0, h]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[0, h] = l_prev * alpha + p.sum(-1, keepdims=True)
            o_ref[0, h] = o_ref[0, h] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32
            )
            m_ref[0, h] = m_new
        return carry

    jax.lax.fori_loop(0, n_tiles, body, 0)


def paged_decode_attention_pallas(
    q: jax.Array,  # (B, H, dh) — one new token per sequence
    k_pool: jax.Array,  # (n_pool, bs, KV, dh) shared block pool
    v_pool: jax.Array | None,  # (n_pool, bs, KV, dh), or None: V is K's first v_dim lanes
    block_tables: jax.Array,  # (B, n_max_blocks) int32 pool ids per row
    lengths: jax.Array,  # (B,) valid cache length per sequence; 0 for a row not served
    *,
    scale: float | None = None,  # None -> 1/sqrt(dh)
    v_dim: int | None = None,  # with v_pool None: V's lanes of the K head
):
    """Flash-decode over a PAGED KV cache: the online-softmax stream of
    ``decode_attention_pallas``, over the blocks a scalar-prefetched block
    table names (the vLLM-style paged-attention gather, done by DMA
    instead of an HBM materialize).  The work follows each row's live
    blocks ``cdiv(lengths[b], bs)``, not the table's width: the kernel
    copies those blocks alone, a tile at a time, and a row of length 0
    costs one grid step and outputs zeros.

    With ``v_pool`` None (absorbed latent attention: one KV head whose
    first ``v_dim`` lanes are the values) each block is read once for
    both, and the output has ``v_dim`` lanes."""
    b, h, dh = q.shape
    n_pool, bs, kv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    n_t = block_tables.shape[1]
    g = h // kv
    scale = 1.0 / np.sqrt(dh) if scale is None else scale
    shared = v_pool is None
    dv = v_dim if shared else dh
    padded = -(-dh // 128) * 128
    tile = _tile_blocks(n_t, kv * bs * padded * k_pool.dtype.itemsize)

    qg = q.reshape(b, kv, g, dh)
    if kv == 1:  # moving a unit axis: a reshape, no copy of the pool
        kt = k_pool.reshape(n_pool, 1, bs, dh)
    else:
        kt = k_pool.transpose(0, 2, 1, 3)  # (n_pool, KV, BS, dh)
    operands = [qg, kt]
    if not shared:
        operands.append(v_pool.transpose(0, 2, 1, 3))
    n_pools = len(operands) - 1
    row = lambda bi, tbl, lens: (bi, 0, 0, 0)  # noqa: E731

    def build(interpret):
        lanes = dh if interpret else padded
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # block_tables, lengths
            grid=(b,),
            in_specs=[pl.BlockSpec((1, kv, g, dh), row)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_pools,
            out_specs=[
                pl.BlockSpec((1, kv, g, dv), row),
                pl.BlockSpec((1, kv, g, 1), row),
                pl.BlockSpec((1, kv, g, 1), row),
            ],
            scratch_shapes=[pltpu.VMEM((2, kv, tile, bs, lanes), k_pool.dtype)] * n_pools
            + [pltpu.SemaphoreType.DMA((n_pools, 2))],
        )
        return pl.pallas_call(
            functools.partial(_paged_kernel, bs=bs, scale=scale, tile=tile,
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
