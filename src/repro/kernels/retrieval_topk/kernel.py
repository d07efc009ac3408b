"""Blocked MIPS top-k Pallas kernel — the retrieval hot spot of C-FedRAG.

Each data provider scores the query against its corpus shard and returns
its local top-k (paper Alg. 1, "Site-i retrieves m relevant contexts with
distance metrics").  On TPU this is a (Q, D) x (D, N) matmul on the MXU
fused with an on-chip running top-k merge, so candidate scores never
round-trip to HBM.

Tiling: grid (Q/BQ, N/BN); for a fixed query block the N-axis is the
innermost (arbitrary) dimension and the (BQ, K) running top-k lives in the
revisited output block (VMEM-resident across the whole N sweep).
BQ/BN default to 128/512 — MXU-aligned (128 lanes) and a working set of
BQ*D + BN*D + BQ*BN well under VMEM at D<=1024.  Small query batches clamp
BQ down, rounded up to a sublane multiple of 8 so the block stays
VPU/MXU-tileable.

Merge strategy: a SINGLE descending sort of the concatenated (BQ, K+BN)
candidate block, then keep the first K lanes — one fused pass replaces
the former K sequential argmax-extraction sweeps, so merge cost no longer
scales with K.  Two equivalent implementations, auto-selected:

  xla      ``lax.sort_key_val`` (stable) — interpret mode on the CPU, where
           the sort primitive lowers natively
  bitonic  an explicit compare-exchange network of roll/where ops (padded
           to a power of two, index tie-break) — every op is VPU-native,
           for compiled TPU where Mosaic has no sort lowering

Compiled or interpreted follows ``repro.kernels.on_backend``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import on_backend

_I32_MAX = jnp.iinfo(jnp.int32).max


def _bitonic_topk_merge(scores, idx, k):
    """Descending bitonic sort of (scores, idx) pairs along the last axis,
    returning the first k columns.  scores: (R, C) f32; idx: (R, C) i32.
    Ties prefer the smaller index (matches lax.top_k).  Pure roll/where
    compare-exchange network — every op is VPU-native on TPU."""
    r, c = scores.shape
    p = 1 << max(c - 1, 1).bit_length()  # next power of two >= c (min 2)
    if p != c:
        scores = jnp.pad(scores, ((0, 0), (0, p - c)), constant_values=-jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, p - c)), constant_values=_I32_MAX)
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, p), 1)
    stage = 2
    while stage <= p:
        step = stage // 2
        while step >= 1:
            upper = (lane & step) != 0  # this lane holds the pair's upper element
            ps = jnp.where(upper, jnp.roll(scores, step, 1), jnp.roll(scores, -step, 1))
            pi = jnp.where(upper, jnp.roll(idx, step, 1), jnp.roll(idx, -step, 1))
            desc = (lane & stage) == 0  # block direction (final stage: all desc)
            self_greater = (scores > ps) | ((scores == ps) & (idx < pi))
            want_max = desc != upper  # desc block: lower lane takes the max
            take_self = self_greater == want_max
            scores = jnp.where(take_self, scores, ps)
            idx = jnp.where(take_self, idx, pi)
            step //= 2
        stage *= 2
    return scores[:, :k], idx[:, :k]


def _sort_topk_merge(scores, idx, k):
    """Stable descending sort via the XLA sort primitive.  Stability +
    concat order (running list before the new block) preserves the
    smaller-index tie preference of lax.top_k."""
    neg_s, si = jax.lax.sort_key_val(-scores, idx, dimension=-1)
    return -neg_s[:, :k], si[:, :k]


_MERGES = {"xla": _sort_topk_merge, "bitonic": _bitonic_topk_merge}


def _kernel(q_ref, c_ref, s_ref, i_ref, *, k: int, bn: int, n_valid: int, merge: str):
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        s_ref[...] = jnp.full_like(s_ref, -jnp.inf)
        i_ref[...] = jnp.full_like(i_ref, _I32_MAX)

    q = q_ref[...].astype(jnp.float32)  # (BQ, D)
    c = c_ref[...].astype(jnp.float32)  # (BN, D)
    blk = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BQ, BN)
    gidx = nj * bn + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    blk = jnp.where(gidx < n_valid, blk, -jnp.inf)  # mask corpus padding

    cand_s = jnp.concatenate([s_ref[...], blk], axis=-1)
    cand_i = jnp.concatenate([i_ref[...], gidx], axis=-1)
    new_s, new_i = _MERGES[merge](cand_s, cand_i, k)
    s_ref[...] = new_s
    i_ref[...] = new_i


def retrieval_topk_pallas(
    queries: jax.Array,
    corpus: jax.Array,
    k: int,
    *,
    bq: int = 128,
    bn: int = 512,
    merge: str | None = None,
):
    """queries: (Q, D); corpus: (N, D).  Returns (scores (Q,k) f32, idx (Q,k) i32).

    Q and N are padded up to block multiples internally; padded corpus rows
    are masked with -inf, padded query rows are sliced off.  ``merge``
    defaults to the XLA sort primitive under the interpreter and the
    bitonic network when compiled.
    """
    q, d = queries.shape
    n = corpus.shape[0]
    # clamp the query block to the batch, rounded up to a sublane multiple
    # of 8 so tiny Q never produces a non-MXU-aligned block shape
    bq = min(bq, max(8, q))
    bq = -(-bq // 8) * 8
    qp = (q + bq - 1) // bq * bq
    np_ = (n + bn - 1) // bn * bn
    if qp != q:
        queries = jnp.pad(queries, ((0, qp - q), (0, 0)))
    if np_ != n:
        corpus = jnp.pad(corpus, ((0, np_ - n), (0, 0)))

    grid = (qp // bq, np_ // bn)

    def build(interpret):
        how = merge or ("xla" if interpret else "bitonic")
        return pl.pallas_call(
            functools.partial(_kernel, k=k, bn=bn, n_valid=n, merge=how),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
                pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
                pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((qp, k), jnp.float32),
                jax.ShapeDtypeStruct((qp, k), jnp.int32),
            ],
            interpret=interpret,
        )

    scores, idx = on_backend(build)(queries, corpus)
    return scores[:q], idx[:q]
