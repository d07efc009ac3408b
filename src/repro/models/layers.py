"""Core transformer layers: RMSNorm, RoPE, GQA attention, SwiGLU MLP.

Pure functions: ``*_specs(cfg)`` builds the ParamSpec subtree,
``*_apply(cfg, pol, params, ...)`` runs it.  All matmuls run in
``cfg.dtype`` (bf16) with f32 softmax/norm accumulation.

Attention impls:
  naive      materialized S_q x S_k logits (small seq, oracle)
  flash_jnp  lax.scan over KV chunks with online softmax — the dry-run /
             XLA production path (O(S·chunk) memory, exact)
  pallas     kernels/flash_attention (compiled on TPU, interpreted on the CPU)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec
from repro.runtime.sharding import ShardingPolicy

# --------------------------------------------------------------------- #
# norms / rope
# --------------------------------------------------------------------- #


def rmsnorm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))  # (hd/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# attention cores  (q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd))
# --------------------------------------------------------------------- #


def _gqa_logits(q, k):
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    qr = q.reshape(b, sq, kv, h // kv, hd)
    return jnp.einsum(
        "bqkgd,bskd->bkgqs", qr, k, preferred_element_type=jnp.float32
    )


def _gqa_out(probs, v, out_dtype):
    b, kv, g, sq, sk = probs.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, kv * g, v.shape[-1]).astype(out_dtype)


def naive_attention(q, k, v, *, causal: bool, q_offset=0):
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = _gqa_logits(q, k) * scale  # (B,KV,G,Sq,Sk) f32
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return _gqa_out(probs, v, q.dtype)


def flash_jnp_attention(q, k, v, *, causal: bool, chunk: int, q_offset=0, unroll=False):
    """Online-softmax over KV chunks (exact; O(Sq*chunk) live memory)."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert sk % chunk == 0, (sk, chunk)
    n = sk // chunk
    g = h // kv
    scale = 1.0 / np.sqrt(hd)
    qr = q.reshape(b, sq, kv, g, hd)
    ks = k.reshape(b, n, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    qpos = q_offset + jnp.arange(sq)

    def body(carry, kc_vc):
        m, l, acc = carry
        (kc, vc), i = kc_vc
        logits = (
            jnp.einsum("bqkgd,bskd->bkgqs", qr, kc, preferred_element_type=jnp.float32)
            * scale
        )  # (B,KV,G,Sq,chunk)
        if causal:
            kpos = i * chunk + jnp.arange(chunk)
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask, logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0), ((ks, vs), jnp.arange(n)),
        unroll=n if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)


def attention_core(cfg: ModelConfig, q, k, v, *, causal: bool, q_offset=0):
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(q, k, v, causal=causal, q_offset=q_offset)
    if cfg.attn_impl == "flash_jnp" and k.shape[1] > cfg.attn_chunk:
        return flash_jnp_attention(
            q, k, v, causal=causal, chunk=cfg.attn_chunk, q_offset=q_offset,
            unroll=cfg.scan_unroll,
        )
    return naive_attention(q, k, v, causal=causal, q_offset=q_offset)


# --------------------------------------------------------------------- #
# attention block
# --------------------------------------------------------------------- #


def attn_specs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    s = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"), "fan_in", fan_in_dims=(0,)),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), "fan_in", fan_in_dims=(0,)),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), "fan_in", fan_in_dims=(0,)),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"), "fan_in", fan_in_dims=(0, 1)),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), ("norm",), "ones")
        s["k_norm"] = ParamSpec((hd,), ("norm",), "ones")
    return s


def attn_qkv(cfg: ModelConfig, pol: ShardingPolicy, p, x, positions):
    """Project + rope + qk-norm.  x: (B,S,d) -> q,k,v."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    q = pol.shard(q, "act_batch", "act_seq", "act_heads", None)
    k = pol.shard(k, "act_batch", "act_seq", "act_kv_heads", None)
    v = pol.shard(v, "act_batch", "act_seq", "act_kv_heads", None)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(cfg: ModelConfig, pol: ShardingPolicy, p, x, positions, *, causal=None):
    causal = cfg.causal if causal is None else causal
    q, k, v = attn_qkv(cfg, pol, p, x, positions)
    out = attention_core(cfg, q, k, v, causal=causal)
    out = pol.shard(out, "act_batch", "act_seq", "act_heads", None)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return pol.shard(out, "act_batch", "act_seq", "act_embed")


# --------------------------------------------------------------------- #
# latent attention (MLA), absorbed: the paged serving path
# --------------------------------------------------------------------- #


def mla_specs(cfg: ModelConfig) -> dict:
    """DeepSeek-V3 attention without a query LoRA: ``wq`` d -> heads x
    (nope + rope); ``wkv_a`` d -> latent + one shared rope key; ``kv_norm``
    the RMSNorm of the latent; ``wkv_b`` latent -> heads x (k_nope + v);
    ``wo`` heads x v -> d."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((d, h, nope + rope), ("embed", "heads", "head_dim"), "fan_in", fan_in_dims=(0,)),
        "wkv_a": ParamSpec((d, r + rope), ("embed", None), "fan_in", fan_in_dims=(0,)),
        "kv_norm": ParamSpec((r,), ("norm",), "ones"),
        "wkv_b": ParamSpec((r, h, nope + dv), (None, "heads", "head_dim"), "fan_in", fan_in_dims=(0,)),
        "wo": ParamSpec((h, dv, d), ("heads", "head_dim", "embed"), "fan_in", fan_in_dims=(0, 1)),
    }


def mla_qkv(cfg: ModelConfig, pol: ShardingPolicy, p, x, positions):
    """Absorbed MLA projections.  x: (B,S,d) -> ``q`` (B,S,H,r+rope), each
    head's nope query folded through its key up-projection W_uk into the
    latent's space beside its roped part; ``latent`` (B,S,1,r+rope), the
    normed latent beside the roped shared key — the one cached head, whose
    first ``r`` lanes are also the values."""
    dt = x.dtype
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q = pol.shard(q, "act_batch", "act_seq", "act_heads", None)
    kv = x @ p["wkv_a"].astype(dt)  # (B,S,r+rope)
    c = rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
    q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    q_lat = jnp.einsum("bshk,chk->bshc", q[..., :nope], p["wkv_b"][..., :nope].astype(dt))
    return jnp.concatenate([q_lat, q_rope], -1), jnp.concatenate([c[..., None, :], k_rope], -1)


def mla_out(cfg: ModelConfig, pol: ShardingPolicy, p, o):
    """Attention over the latent values (B,S,H,r) -> W_uv per head, then
    the output projection -> (B,S,d)."""
    dt = o.dtype
    v = jnp.einsum("bshc,chk->bshk", o, p["wkv_b"][..., cfg.qk_nope_head_dim:].astype(dt))
    out = jnp.einsum("bshk,hkd->bsd", v, p["wo"].astype(dt))
    return pol.shard(out, "act_batch", "act_seq", "act_embed")


def _project_paged(cfg, pol, p, x, positions):
    """The paged path's projections: ``(q, k_new, v_new)``; for latent
    attention ``k_new`` is the latent head and ``v_new`` None (the values
    are its first ``kv_lora_rank`` lanes)."""
    if cfg.mla:
        q, latent = mla_qkv(cfg, pol, p, x, positions)
        return q, latent, None
    return attn_qkv(cfg, pol, p, x, positions)


def _attn_out_paged(cfg, pol, p, o, dt):
    if cfg.mla:
        return mla_out(cfg, pol, p, o)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return pol.shard(out, "act_batch", "act_seq", "act_embed")


def attn_decode(cfg: ModelConfig, pol: ShardingPolicy, p, x, k_cache, v_cache, pos):
    """Single-token decode.  x: (B,1,d); caches: (B,S,KV,hd); pos: scalar
    write position, or (B,) per-row positions for ragged batches (each row
    writes its own cache slot and attends to its own prefix)."""
    b, s = x.shape[0], k_cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = attn_qkv(cfg, pol, p, x, positions)
    if per_row:
        slot = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1) == pos[:, None]
        k_cache = jnp.where(slot[..., None, None], k_new.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(slot[..., None, None], v_new.astype(v_cache.dtype), v_cache)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
    k_cache = pol.shard(k_cache, "cache_batch", "cache_seq", "cache_kv", None)
    v_cache = pol.shard(v_cache, "cache_batch", "cache_seq", "cache_kv", None)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = _gqa_logits(q, k_cache.astype(q.dtype)) * scale  # (B,KV,G,1,S)
    kpos = jnp.arange(s)
    valid = (kpos[None, :] <= pos[:, None]).reshape(b, 1, 1, 1, s) if per_row else (kpos <= pos)
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = _gqa_out(probs, v_cache.astype(q.dtype), q.dtype)  # (B,1,H,hd)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, k_cache, v_cache


def _paged_attn_sharded(cfg: ModelConfig, q, k_new, v_new, k_pool, v_pool,
                        block_tables, q_start, q_len, block_size: int, mesh):
    """Distributed write-then-attend over a SHARDED block pool.

    ``k_pool``/``v_pool``: ``(n_shards, n_local + 1, block_size, KV, hd)``
    laid out ``P("data", ...)`` — each device holds its shard's blocks
    plus a per-shard trash block at local index ``n_local``.
    ``block_tables`` carries GLOBAL block ids (shard ``b // n_local``,
    local id ``b % n_local``; the global trash id ``n_shards * n_local``
    maps to every shard's local trash automatically, since its "shard"
    equals ``n_shards`` and matches nobody).

    Each shard scatters only the fresh lanes whose target block it owns
    (everything else lands in its local trash) and runs the
    ``kernels/chunked_prefill`` partials over its own table entries, with
    non-owned entries masked to exact zeros.  The allocator's row
    affinity puts ALL of a row's blocks on one shard, so the
    ``dist_decode.combine_partials`` merge passes the owner's partials
    through bitwise — an N-shard run is bit-identical to the 1-shard run
    (asserted in tests/test_sharded_serving.py).

    Returns ``(out, k_pool, v_pool)`` with ``out``: ``(B, W, H, hd)``
    (wo projection is the caller's, outside the shard_map).
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.chunked_prefill.ref import mixed_prefill_partials
    from repro.serving.dist_decode import combine_partials

    b, w, h, dh = q.shape
    kv = k_pool.shape[3]
    n_local = k_pool.shape[1] - 1
    s_pad = block_tables.shape[1] * block_size
    rows = jnp.arange(b)

    def body(q, k_sh, v_sh, k_new, v_new, tables, q_start, q_len):
        k_sh, v_sh = k_sh[0], v_sh[0]  # (n_local+1, bs, KV, hd)
        my = jax.lax.axis_index("data")
        owned = (tables // n_local) == my  # (B, n_t)
        loc_tbl = jnp.where(owned, tables % n_local, n_local)
        lane = jnp.arange(w)
        live = lane[None, :] < q_len[:, None]
        pos_c = jnp.minimum(q_start[:, None] + lane[None, :], s_pad - 1)
        bid_g = tables[rows[:, None], pos_c // block_size]
        mine = live & ((bid_g // n_local) == my)
        bid = jnp.where(mine, bid_g % n_local, n_local)
        off = pos_c % block_size
        k_sh = k_sh.at[bid, off].set(k_new.astype(k_sh.dtype))
        v_sh = v_sh.at[bid, off].set(v_new.astype(v_sh.dtype))
        desc = jnp.stack(
            [rows, q_start, q_len, q_start + q_len], axis=1
        ).astype(jnp.int32)
        o, m, l = mixed_prefill_partials(q, k_sh, v_sh, loc_tbl, desc, owned=owned)
        out = combine_partials(o, m, l, axis_name="data")  # (B,KV,G,W,dh)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, w, kv * (h // kv), dh)
        return out.astype(q.dtype), k_sh[None], v_sh[None]

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P("data"), P("data"), P(), P(), P(), P(), P()),
        out_specs=(P(), P("data"), P("data")),
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, k_new, v_new, block_tables, q_start, q_len)


def attn_decode_paged(cfg: ModelConfig, pol: ShardingPolicy, p, x, k_pool, v_pool, pos, block_tables, block_size: int, mesh=None):
    """Single-token decode against a PAGED KV cache.

    ``k_pool``/``v_pool``: ``(n_pool, block_size, KV, hd)`` shared block
    pool (this layer's slice); ``block_tables``: ``(B, n_max_blocks)``
    int32 mapping each row's logical block ``i`` (positions ``[i*bs,
    (i+1)*bs)``) to a pool block.  ``pos`` is always per-row ``(B,)`` in
    paged mode.  The new K/V lands at ``pool[table[pos // bs], pos % bs]``.

    Attention impl follows ``cfg.attn_impl`` — the same kernels-vs-layers
    split the contiguous decode path has:
      * default (XLA): gather the ``(B, n_max_blocks * bs)`` view and run
        the masked softmax inline — identical values, shapes, and mask
        arithmetic to the contiguous ``attn_decode`` whenever
        ``n_max_blocks * bs`` equals the contiguous ``cache_len``, which
        is what makes the paged engine bit-identical to the contiguous
        baseline.  Unallocated table entries point at the engine's trash
        block: their lanes are always behind the ``kpos <= pos`` mask, so
        whatever they hold contributes exactly 0 to softmax.
      * ``attn_impl="pallas"``: ``kernels/decode_attention``'s paged
        flash-decode kernel — the scalar-prefetched block table drives
        the K/V BlockSpec index maps, so the gather never materializes in
        HBM (interpreted on the CPU; numerically equal to the XLA path
        within flash-softmax reassociation tolerance, parity-tested in
        tests/test_models.py).
    """
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _project_paged(cfg, pol, p, x, pos[:, None])
    if k_pool.ndim == 5:
        # sharded pool (n_shards, n_local+1, bs, KV, hd): decode is the
        # W=1 case of the distributed mixed dispatch.  A free slot's
        # all-trash table matches no shard, so its (discarded) lane
        # outputs exact zeros instead of trash-block garbage
        out, k_pool, v_pool = _paged_attn_sharded(
            cfg, q, k_new, v_new, k_pool, v_pool, block_tables,
            pos, jnp.ones((b,), jnp.int32), block_size, mesh,
        )
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        return out, k_pool, v_pool
    rows = jnp.arange(b)
    bid = block_tables[rows, pos // block_size]  # (B,) pool block per row
    off = pos % block_size
    # rows own disjoint blocks (the pool allocator guarantees it), so the
    # (bid, off) scatter targets are distinct across live rows
    k_pool = k_pool.at[bid, off].set(k_new[:, 0].astype(k_pool.dtype))
    if v_pool is not None:
        v_pool = v_pool.at[bid, off].set(v_new[:, 0].astype(v_pool.dtype))
    # latent attention: one head, the values its first kv_lora_rank lanes
    scale, v_dim = (cfg.attn_scale, cfg.kv_lora_rank) if cfg.mla else (None, None)
    if cfg.attn_impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops

        out = da_ops.paged_decode_attention(
            q[:, 0], k_pool, v_pool, block_tables, pos + 1, use_pallas=True,
            scale=scale, v_dim=v_dim,
        )[:, None]  # (B,1,H,hd)
    else:
        s_pad = block_tables.shape[1] * block_size
        k_view = k_pool[block_tables].reshape(b, s_pad, *k_pool.shape[2:])
        v_view = (k_view[..., :v_dim] if v_pool is None
                  else v_pool[block_tables].reshape(b, s_pad, *v_pool.shape[2:]))
        scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
        logits = _gqa_logits(q, k_view.astype(q.dtype)) * scale  # (B,KV,G,1,S_pad)
        kpos = jnp.arange(s_pad)
        valid = (kpos[None, :] <= pos[:, None]).reshape(b, 1, 1, 1, s_pad)
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = _gqa_out(probs, v_view.astype(q.dtype), q.dtype)  # (B,1,H,hd)
    out = _attn_out_paged(cfg, pol, p, out, x.dtype)
    return out, k_pool, v_pool


def attn_mixed_paged(cfg: ModelConfig, pol: ShardingPolicy, p, x, k_pool, v_pool,
                     positions, block_tables, block_size: int, q_len, mesh=None):
    """UNIFIED mixed prefill+decode attention against a paged KV cache:
    one dispatch serves any mix of cold prefill chunks, warm suffix
    chunks riding shared prefix blocks, and 1-token decode rows.

    ``x``: ``(B, W, d)`` — W query lanes per row, of which the first
    ``q_len[b]`` are live (a decode row is ``q_len == 1``; an idle slot
    is ``q_len == 0``).  ``positions``: ``(B, W)`` absolute positions
    ``q_start[b] + lane``.  Write-then-attend: the live lanes' fresh K/V
    scatter into ``pool[table[pos // bs], pos % bs]`` FIRST (dead lanes
    target the trash block, never a neighbor's), then attention reads
    the pool alone — no fresh-K/V overlay, no HBM gather of a prefix
    view.  For a decode row this is exactly ``attn_decode_paged``'s
    scatter + mask arithmetic; for prefill lanes the pool round-trip is
    lossless at pool dtype == activation dtype, so chunked fill equals
    the dense prefill per token.  Because every row reads pool-dtype
    K/V for prefix AND fresh lanes alike, hit-vs-miss consistency holds
    at any pool dtype (the restriction the overlay path had to impose).

    Attention impl follows ``cfg.attn_impl``:
      * default (XLA): gather the padded view, mask each lane to its
        causal span ``kpos <= position`` within ``kv_len``, re-zero
        probs under the mask (exact identity for live lanes; makes dead
        lanes output exactly 0).
      * ``attn_impl="pallas"``: ``kernels/chunked_prefill``'s unified
        kernel — descriptors + block table ride scalar prefetch, pool
        blocks stream straight into VMEM (interpreted on the CPU).

    Latent attention (``cfg.mla``) takes its one latent head as
    ``k_pool`` and ``v_pool`` None: the same scatter and kernel call, with
    the values the latent's first ``kv_lora_rank`` lanes and the
    published softmax scale.

    Returns ``(o, k_pool, v_pool)`` with the fresh K/V already resident.
    """
    b, w = x.shape[0], x.shape[1]
    q, k_new, v_new = _project_paged(cfg, pol, p, x, positions)
    if k_pool.ndim == 5:
        # sharded pool: distributed dispatch — per-shard scatter +
        # chunked-prefill partials, merged by dist_decode's combine
        out, k_pool, v_pool = _paged_attn_sharded(
            cfg, q, k_new, v_new, k_pool, v_pool, block_tables,
            positions[:, 0], q_len, block_size, mesh,
        )
        out = pol.shard(out, "act_batch", "act_seq", "act_heads", None)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        out = pol.shard(out, "act_batch", "act_seq", "act_embed")
        return out, k_pool, v_pool
    s_pad = block_tables.shape[1] * block_size
    lane = jnp.arange(w)
    live = lane[None, :] < q_len[:, None]  # (B, W)
    pos_c = jnp.minimum(positions, s_pad - 1)
    bid = jnp.where(
        live,
        block_tables[jnp.arange(b)[:, None], pos_c // block_size],
        k_pool.shape[0] - 1,  # trash block
    )
    off = pos_c % block_size
    # live lanes hit disjoint (bid, off) slots across rows (the allocator
    # guarantees block ownership); dead-lane collisions land in trash
    k_pool = k_pool.at[bid, off].set(k_new.astype(k_pool.dtype))
    if v_pool is not None:
        v_pool = v_pool.at[bid, off].set(v_new.astype(v_pool.dtype))
    q_start = positions[:, 0]
    kv_len = q_start + q_len
    # latent attention: one head, the values its first kv_lora_rank lanes
    scale, v_dim = (cfg.attn_scale, cfg.kv_lora_rank) if cfg.mla else (None, None)
    if cfg.attn_impl == "pallas":
        from repro.kernels.chunked_prefill import ops as cp_ops

        desc = jnp.stack(
            [jnp.arange(b), q_start, q_len, kv_len], axis=1
        ).astype(jnp.int32)
        out = cp_ops.mixed_prefill_attention(
            q, k_pool, v_pool, block_tables, desc, use_pallas=True, scale=scale, v_dim=v_dim,
        )  # (B,W,H,hd)
    else:
        k_view = k_pool[block_tables].reshape(b, s_pad, *k_pool.shape[2:])
        v_view = (k_view[..., :v_dim] if v_pool is None
                  else v_pool[block_tables].reshape(b, s_pad, *v_pool.shape[2:]))
        scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
        logits = _gqa_logits(q, k_view.astype(q.dtype)) * scale  # (B,KV,G,W,S_pad)
        kpos = jnp.arange(s_pad)
        valid = (
            (kpos[None, None, :] <= positions[..., None])
            & (kpos[None, None, :] < kv_len[:, None, None])
            & live[..., None]
        )  # (B, W, S_pad)
        logits = jnp.where(valid[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(valid[:, None, None], probs, 0.0)
        out = _gqa_out(probs, v_view.astype(q.dtype), q.dtype)  # (B,W,H,hd)
    out = pol.shard(out, "act_batch", "act_seq", "act_heads", None)
    out = _attn_out_paged(cfg, pol, p, out, x.dtype)
    return out, k_pool, v_pool


# --------------------------------------------------------------------- #
# SwiGLU MLP
# --------------------------------------------------------------------- #


def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wg": ParamSpec((d, f), ("embed", "mlp"), "fan_in", fan_in_dims=(0,)),
        "wu": ParamSpec((d, f), ("embed", "mlp"), "fan_in", fan_in_dims=(0,)),
        "wd": ParamSpec((f, d), ("mlp", "embed"), "fan_in", fan_in_dims=(0,)),
    }


def mlp_apply(cfg: ModelConfig, pol: ShardingPolicy, p, x):
    dt = x.dtype
    h = jax.nn.silu(x @ p["wg"].astype(dt)) * (x @ p["wu"].astype(dt))
    h = pol.shard(h, "act_batch", "act_seq", "act_ff")
    out = h @ p["wd"].astype(dt)
    return pol.shard(out, "act_batch", "act_seq", "act_embed")


# --------------------------------------------------------------------- #
# embeddings / head
# --------------------------------------------------------------------- #


def embed_specs(cfg: ModelConfig) -> dict:
    s = {"tok": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), "normal")}
    return s


def head_specs(cfg: ModelConfig) -> dict:
    if cfg.tie_embeddings:
        return {}
    return {"w": ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"), "fan_in", fan_in_dims=(0,))}


def embed_apply(cfg: ModelConfig, pol: ShardingPolicy, p, tokens):
    out = jnp.take(p["tok"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    return pol.shard(out, "act_batch", "act_seq", "act_embed")


def head_apply(cfg: ModelConfig, pol: ShardingPolicy, params, x):
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
    logits = (x @ w.astype(x.dtype)).astype(jnp.dtype(cfg.logit_dtype))
    return pol.shard(logits, "act_batch", "act_seq", "act_vocab")
