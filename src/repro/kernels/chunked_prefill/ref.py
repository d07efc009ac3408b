"""Pure-jnp oracle for the unified chunked-prefill / mixed-decode kernel.

One dispatch serves any mix of rows — cold prefills, warm suffix
prefills (prefix K/V already resident in the pool), and 1-token decode
steps — described per row by ``desc[r] = (slot, q_start, q_len, kv_len)``:

* ``slot``     row in ``block_tables`` whose pool blocks hold this
               sequence's K/V (fresh tokens are scattered into the pool
               *before* attention, so the kernel only ever reads the pool)
* ``q_start``  logical position of query lane 0
* ``q_len``    number of live query lanes (lanes >= q_len output exact 0)
* ``kv_len``   total valid K/V length (= q_start + q_len for causal fill)

Lane ``j`` attends position ``kpos`` iff ``kpos <= q_start + j`` and
``kpos < kv_len`` — causal within the row's lane span, never past the
row's valid cache.  A decode row is simply ``q_len == 1``.
"""
import jax
import jax.numpy as jnp
import numpy as np


def mixed_prefill_attention_ref(q, k_pool, v_pool, block_tables, desc, scale=None, v_dim=None):
    """Oracle: gather each row's contiguous pool view, dense masked softmax.

    q:            (R, W, H, dh) — W ragged query lanes per row
    k_pool/v_pool:(n_pool, bs, KV, dh) shared block pool
    block_tables: (B, n_t) int32 pool ids per cache slot
    desc:         (R, 4) int32 rows (slot, q_start, q_len, kv_len)

    Invalid lanes (j >= q_len) produce exactly 0 — the masked softmax
    would give uniform probs over all-(-1e30) logits, so probs are zeroed
    wherever the mask is false (an exact identity for live lanes: masked
    positions already carry exp(-1e30 - m) == +0.0).

    ``v_pool`` None: the values are the first ``v_dim`` lanes of K (a
    latent head); ``scale`` None is 1/sqrt(dh).
    """
    r, w, h, dh = q.shape
    bs, kv = k_pool.shape[1], k_pool.shape[2]
    tbl = block_tables[desc[:, 0]]  # (R, n_t)
    s_pad = tbl.shape[1] * bs
    k_view = k_pool[tbl].reshape(r, s_pad, kv, dh).astype(jnp.float32)
    if v_pool is None:
        v_view = k_view[..., :v_dim]
    else:
        v_view = v_pool[tbl].reshape(r, s_pad, kv, dh).astype(jnp.float32)
    qr = q.astype(jnp.float32).reshape(r, w, kv, h // kv, dh)
    logits = jnp.einsum("rwkgd,rskd->rkgws", qr, k_view)
    logits = logits / np.sqrt(dh) if scale is None else logits * scale
    lane = jnp.arange(w)
    kpos = jnp.arange(s_pad)
    qpos = desc[:, 1][:, None] + lane[None, :]  # (R, W)
    valid = (
        (kpos[None, None, :] <= qpos[:, :, None])
        & (kpos[None, None, :] < desc[:, 3][:, None, None])
        & (lane[None, :, None] < desc[:, 2][:, None, None])
    )  # (R, W, S)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(valid[:, None, None], p, 0.0)
    out = jnp.einsum("rkgws,rskd->rwkgd", p, v_view)
    return out.reshape(r, w, h, v_view.shape[-1]).astype(q.dtype)


def mixed_prefill_partials(q, k_pool, v_pool, block_tables, desc, owned=None):
    """Flash-softmax partials of the mixed oracle — the per-shard half of
    the distributed dispatch.

    Same contract as ``mixed_prefill_attention_ref`` but stops before
    normalization, returning ``(o, m, l)``: un-normalized weighted values
    ``o`` (R, KV, G, W, dh), row max ``m`` and partition sum ``l`` (R,
    KV, G, W, 1), ready for ``serving/dist_decode.combine_partials``.

    ``owned`` (same leading shape as ``block_tables``, bool) marks the
    block-table entries resident on this shard; non-owned positions are
    masked out of ``valid``.  A shard owning NONE of a row's blocks (row
    affinity) contributes exact zeros — ``m = -1e30``, ``l = 0``,
    ``o = 0`` — so the cross-shard combine passes the owner's partials
    through bitwise.  ``owned=None`` means "owns everything": with one
    shard the combine then reduces to ``o / l``, the bitwise reference
    for every N-shard run.
    """
    r, w, h, dh = q.shape
    bs, kv = k_pool.shape[1], k_pool.shape[2]
    tbl = block_tables[desc[:, 0]]  # (R, n_t)
    s_pad = tbl.shape[1] * bs
    k_view = k_pool[tbl].reshape(r, s_pad, kv, dh).astype(jnp.float32)
    v_view = v_pool[tbl].reshape(r, s_pad, kv, dh).astype(jnp.float32)
    qr = q.astype(jnp.float32).reshape(r, w, kv, h // kv, dh)
    logits = jnp.einsum("rwkgd,rskd->rkgws", qr, k_view) / np.sqrt(dh)
    lane = jnp.arange(w)
    kpos = jnp.arange(s_pad)
    qpos = desc[:, 1][:, None] + lane[None, :]  # (R, W)
    valid = (
        (kpos[None, None, :] <= qpos[:, :, None])
        & (kpos[None, None, :] < desc[:, 3][:, None, None])
        & (lane[None, :, None] < desc[:, 2][:, None, None])
    )  # (R, W, S)
    if owned is not None:
        own_pos = jnp.repeat(owned[desc[:, 0]], bs, axis=1)  # (R, s_pad)
        valid = valid & own_pos[:, None, :]
    vb = valid[:, None, None]  # (R, 1, 1, W, S)
    logits = jnp.where(vb, logits, -1e30)
    m = logits.max(-1, keepdims=True)
    e = jnp.exp(logits - m)
    e = jnp.where(vb, e, 0.0)  # all-masked rows: l and o exactly 0
    l = e.sum(-1, keepdims=True)
    o = jnp.einsum("rkgws,rskd->rkgwd", e, v_view)
    return o, m, l
