"""Distributed flash-decode: single-token attention over a KV cache whose
SEQUENCE dim is sharded across a mesh axis (§Perf cell A3 as runnable code).

Each shard computes (o, m, l) softmax partials over its cache slice, then
``combine_partials`` — a 3-tensor combine (pmax + 2 psums of per-head
scalars/rows) — produces the exact global attention: the same math as the
list-based ``kernels/decode_attention.combine_partials``, validated in
tests/test_kernels.py and tests/test_sharded_serving.py.

``combine_partials`` here is THE shared cross-shard merge: the sharded
paged engine's distributed mixed dispatch (``layers.attn_mixed_paged`` /
``attn_decode_paged`` with a 5-D sharded pool) imports it rather than
re-deriving the merge.  Its bit-parity contract: when a query row's KV
blocks are all resident on ONE shard (the allocator's row-affinity
invariant) and every other shard contributes exact-zero partials
(``m = -1e30``, ``l = 0``, ``o = 0`` — the trash-block masking contract),
the combine returns the owner's ``o / l`` bitwise: ``pmax`` over
``{m, -1e30, ...}`` is ``m``, the owner's scale is ``exp(0) = 1.0``
exactly, non-owner scales underflow to ``+0.0`` exactly, and adding
``±0.0`` in the psums preserves the owner's bits.  So an N-shard run is
bit-identical to the 1-shard run of the same partials-form attention.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def combine_partials(o, m, l, *, axis_name: str):
    """Merge per-shard flash-softmax partials across ``axis_name``.

    ``o``: un-normalized weighted values (``sum_j e_ij v_j`` over the
    shard's keys), ``m``: the shard's row max (masked rows carry
    ``-1e30``), ``l``: the shard's partition sum — all with the reduced
    key dim kept at size 1 on ``m``/``l``.  Returns the exact global
    ``softmax @ V`` output (same shape as ``o``)."""
    m_g = jax.lax.pmax(m, axis_name)
    scale = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * scale, axis_name)
    o_g = jax.lax.psum(o * scale, axis_name)
    return o_g / jnp.maximum(l_g, 1e-30)


def _local_partials(q, k_loc, v_loc, lengths, *, axis_name):
    """Per-shard partials + cross-shard flash-decode merge."""
    axis = jax.lax.axis_index(axis_name)
    shard_len = k_loc.shape[1]
    local_valid = jnp.clip(lengths - axis * shard_len, 0, shard_len)
    b, h, dh = q.shape
    kv = k_loc.shape[2]
    qr = q.astype(jnp.float32).reshape(b, kv, h // kv, dh)
    logits = jnp.einsum("bkgd,bskd->bkgs", qr, k_loc.astype(jnp.float32)) / np.sqrt(dh)
    valid = jnp.arange(shard_len)[None, None, None, :] < local_valid[:, None, None, None]
    logits = jnp.where(valid, logits, -1e30)
    m = logits.max(-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(valid, p, 0.0)  # all-masked shards contribute exact zeros
    l = p.sum(-1, keepdims=True)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_loc.astype(jnp.float32))
    out = combine_partials(o, m, l, axis_name=axis_name).reshape(b, h, dh)
    return out.astype(q.dtype)


def dist_decode_attention(
    q,  # (B, H, dh) replicated over the shard axis
    k_cache,  # (B, S, KV, dh), dim 1 sharded over `axis_name`
    v_cache,
    lengths,  # (B,) global valid lengths
    mesh,
    axis_name: str = "data",
):
    fn = jax.shard_map(
        partial(_local_partials, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(), P(None, axis_name, None, None), P(None, axis_name, None, None), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, lengths)
