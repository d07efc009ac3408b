"""Causal LM assembly: scan-over-blocks, train / prefill / decode steps.

Handles all assigned decoder families:
  dense | moe   uniform blocks (period 1)
  hybrid        Jamba-style period-8 blocks (attn 1:7, MoE every 2nd)
  ssm           all-mamba
  vlm           dense backbone + precomputed patch embeddings merged into
                the first ``n_patches`` positions (frontend stub, DESIGN §5)

Parameters for one scan block are declared once and stacked over
``n_blocks`` (leading "layers" axis) so XLA sees a single rolled loop —
essential for compile time at 40-72 layers on the 512-device dry-run.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models.params import ParamSpec
from repro.runtime.sharding import ShardingPolicy

f32 = jnp.float32


# --------------------------------------------------------------------- #
# parameter declaration
# --------------------------------------------------------------------- #


def _position_specs(cfg: ModelConfig, i: int, ffn: str) -> dict:
    d = cfg.d_model
    s: dict[str, Any] = {"mixer_norm": ParamSpec((d,), ("norm",), "ones")}
    if cfg.mixer_kind(i) == "attn":
        s["attn"] = L.mla_specs(cfg) if cfg.mla else L.attn_specs(cfg)
    else:
        s["mamba"] = M.mamba_specs(cfg)
    if ffn == "moe":
        s["ffn_norm"] = ParamSpec((d,), ("norm",), "ones")
        s["moe"] = MOE.held_moe_specs(cfg) if cfg.experts_held else MOE.moe_specs(cfg)
    elif cfg.d_ff > 0:
        s["ffn_norm"] = ParamSpec((d,), ("norm",), "ones")
        s["mlp"] = L.mlp_specs(cfg)
    return s


def _stack_specs(tree, n: int, axis: str = "layers"):
    return jax.tree.map(
        lambda p: ParamSpec(
            (n,) + p.shape, (axis,) + p.axes, p.init, p.scale,
            tuple(d + 1 for d in p.fan_in_dims),
        ),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _groups(cfg: ModelConfig) -> dict:
    """Layer groups, each ``(layers, FFN kind of each scanned position)``:
    ``lead``, the leading dense layers, when the config has them; then
    ``blocks``, ``n_blocks`` repeats of ``scan_period`` positions."""
    groups = {"lead": (cfg.first_dense, ["dense"])} if cfg.first_dense else {}
    groups["blocks"] = (cfg.n_blocks, [cfg.ffn_kind(cfg.first_dense + j) for j in range(cfg.scan_period)])
    return groups


def param_specs(cfg: ModelConfig) -> dict:
    specs = {"embed": L.embed_specs(cfg), "final_norm": ParamSpec((cfg.d_model,), ("norm",), "ones")}
    for group, (n, ffns) in _groups(cfg).items():
        block = {f"pos{j}": _position_specs(cfg, j, f) for j, f in enumerate(ffns)}
        specs[group] = _stack_specs(block, n)
    specs.update({"head": h} if (h := L.head_specs(cfg)) else {})
    return specs


# --------------------------------------------------------------------- #
# block execution
# --------------------------------------------------------------------- #


def _run_position(cfg, pol, i, pp, h, positions, mode, cache_in, pos, paged=None, ffn=None,
                  live=None, rows=None):
    """One layer (mixer + ffn).  cache_in: per-position cache pytree or None.
    ``ffn``: the layer's FFN kind (default ``cfg.ffn_kind(i)``); ``live``:
    the tokens whose output is used, gathered at ``rows``
    (``moe.live_rows``), for a held-expert layer.
    ``paged``: None (contiguous cache) or ``(block_tables, block_size)``
    (+ ``q_len`` in ``mixed`` mode) — attention then reads/writes K/V
    through the block table (non-attention state is per-slot in both
    layouts).  ``mixed`` is the unified serving mode: each row carries a
    prompt chunk or a single decode token, and the layer scatters fresh
    K/V into the pool before attending, so prompts may resume at any
    chunk boundary.  Latent attention (``cfg.mla``) keeps one pool leaf,
    ``latent``, and runs on the paged modes only.  Returns (h, cache_out,
    aux, routing counters): int32[3] from a held-expert layer, else None."""
    aux = jnp.zeros((), f32)
    stats = None
    ffn = cfg.ffn_kind(i) if ffn is None else ffn
    x = L.rmsnorm(h, pp["mixer_norm"], cfg.norm_eps)
    cache_out = None
    if cfg.mla:
        if mode not in ("mixed", "decode") or paged is None:
            raise NotImplementedError(
                "latent attention runs on the paged serving path only (mixed and "
                f"paged decode steps), not in {mode!r} mode")
        if mode == "decode":
            tables, bs, mesh = paged
            o, lat, _ = L.attn_decode_paged(
                cfg, pol, pp["attn"], x, cache_in["latent"], None, pos, tables, bs, mesh=mesh)
        else:
            tables, bs, q_len, mesh = paged
            o, lat, _ = L.attn_mixed_paged(
                cfg, pol, pp["attn"], x, cache_in["latent"], None,
                positions, tables, bs, q_len, mesh=mesh)
        cache_out = {"latent": lat}
    elif cfg.mixer_kind(i) == "attn":
        if mode == "decode" and paged is not None:
            tables, bs, mesh = paged
            o, k_c, v_c = L.attn_decode_paged(
                cfg, pol, pp["attn"], x, cache_in["k"], cache_in["v"], pos, tables, bs,
                mesh=mesh,
            )
            cache_out = {"k": k_c, "v": v_c}
        elif mode == "mixed":
            tables, bs, q_len, mesh = paged
            o, k_c, v_c = L.attn_mixed_paged(
                cfg, pol, pp["attn"], x, cache_in["k"], cache_in["v"],
                positions, tables, bs, q_len, mesh=mesh,
            )
            cache_out = {"k": k_c, "v": v_c}
        elif mode == "decode":
            o, k_c, v_c = L.attn_decode(cfg, pol, pp["attn"], x, cache_in["k"], cache_in["v"], pos)
            cache_out = {"k": k_c, "v": v_c}
        elif mode == "prefill":
            q, k, v = L.attn_qkv(cfg, pol, pp["attn"], x, positions)
            o = L.attention_core(cfg, q, k, v, causal=cfg.causal)
            o = pol.shard(o, "act_batch", "act_seq", "act_heads", None)
            o = jnp.einsum("bshk,hkd->bsd", o, pp["attn"]["wo"].astype(x.dtype))
            o = pol.shard(o, "act_batch", "act_seq", "act_embed")
            s_len = cache_in["k"].shape[1]
            k_c = jax.lax.dynamic_update_slice_in_dim(cache_in["k"], k.astype(cache_in["k"].dtype), 0, axis=1)
            v_c = jax.lax.dynamic_update_slice_in_dim(cache_in["v"], v.astype(cache_in["v"].dtype), 0, axis=1)
            cache_out = {
                "k": pol.shard(k_c, "cache_batch", "cache_seq", "cache_kv", None),
                "v": pol.shard(v_c, "cache_batch", "cache_seq", "cache_kv", None),
            }
        else:
            o = L.attn_apply(cfg, pol, pp["attn"], x, positions)
    else:
        if mode == "mixed":
            raise NotImplementedError(
                "unified mixed dispatch needs every mixer to be attention: "
                "SSM/conv state folds the whole sequence and cannot restart "
                "mid-prompt"
            )
        if mode == "decode":
            o, conv, ssm = M.mamba_decode(cfg, pol, pp["mamba"], x, cache_in["conv"], cache_in["ssm"])
            cache_out = {"conv": conv, "ssm": ssm}
        else:
            o, (conv, ssm) = M.mamba_apply(cfg, pol, pp["mamba"], x)
            if mode == "prefill":
                cache_out = {"conv": conv, "ssm": ssm}
    h = h + o
    if "ffn_norm" not in pp:  # pure-SSM blocks (mamba2) have no FFN
        return h, cache_out, aux, stats
    x = L.rmsnorm(h, pp["ffn_norm"], cfg.norm_eps)
    if ffn == "moe" and cfg.experts_held:
        o, stats = MOE.held_moe_apply(cfg, pol, pp["moe"], x, live, rows)
    elif ffn == "moe":
        o, aux = MOE.moe_apply(cfg, pol, pp["moe"], x)
    else:
        o = L.mlp_apply(cfg, pol, pp["mlp"], x)
    return h + o, cache_out, aux, stats


def _run_blocks(cfg, pol, params, h, positions, mode="train", cache=None, pos=0, paged=None,
                live=None, rows=None):
    """Scan over blocks, group by group (``_groups``: the leading dense
    layers, then the periodic stack).  cache: per group, a stacked pytree
    (layers leading) or None.  ``paged``: see ``_run_position`` (the block
    table is shared across layers, so it rides in as a closure constant,
    not a scanned leaf).  Returns (h, new_cache, aux_total, routing
    counters int32[3] summed over the held-expert layers, or None)."""
    aux_tot = jnp.zeros((), f32)
    # routing counters ride the carry only where a layer holds experts
    stats_tot = jnp.zeros((len(MOE.ROUTING_COUNTERS),), jnp.int32) if cfg.experts_held else None
    new_cache = {}
    for group, (n, ffns) in _groups(cfg).items():

        def body(carry, xs, ffns=ffns):
            hh, aux_tot, stats_tot = carry
            bp, cache_blk = xs
            out = {}
            for j, ffn in enumerate(ffns):
                c_in = cache_blk.get(f"pos{j}") if cache_blk else None
                hh, c_out, aux, stats = _run_position(
                    cfg, pol, j, bp[f"pos{j}"], hh, positions, mode, c_in, pos, paged,
                    ffn=ffn, live=live, rows=rows,
                )
                if c_out is not None:
                    out[f"pos{j}"] = c_out
                aux_tot = aux_tot + aux
                if stats is not None:
                    stats_tot = stats_tot + stats
            return (hh, aux_tot, stats_tot), (out or None)

        if cfg.remat == "block" and mode == "train":
            body = jax.checkpoint(body)
        if cache is None:
            group_cache = None
        elif group == "lead":
            group_cache = cache["lead"]
        else:  # the periodic stack keeps the top level, as before groups
            group_cache = {k: v for k, v in cache.items() if k != "lead"}
        (h, aux_tot, stats_tot), c = jax.lax.scan(
            body, (h, aux_tot, stats_tot), (params[group], group_cache),
            unroll=n if cfg.scan_unroll else 1,
        )
        if c is not None:
            new_cache.update(c if group == "blocks" else {group: c})
    n_moe = sum(cfg.ffn_kind(i) == "moe" for i in range(cfg.n_layers))
    return h, (new_cache or None), aux_tot / max(n_moe, 1), stats_tot


def _embed_inputs(cfg, pol, params, batch):
    h = L.embed_apply(cfg, pol, params["embed"], batch["tokens"])
    if cfg.frontend == "patches" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(h.dtype)
        h = jnp.concatenate([pe, h[:, pe.shape[1] :, :]], axis=1)
    return h


# --------------------------------------------------------------------- #
# public steps
# --------------------------------------------------------------------- #


def forward(cfg: ModelConfig, pol: ShardingPolicy, params, batch):
    """Full forward -> logits (B,S,V)."""
    tokens = batch["tokens"]
    h = _embed_inputs(cfg, pol, params, batch)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    h, _, aux, _ = _run_blocks(cfg, pol, params, h, positions, mode="train")
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return L.head_apply(cfg, pol, params, h), aux


def sharded_ce(logits, targets, mask):
    """CE that never gathers the vocab dim: logsumexp + one-hot contraction
    both reduce over the (model-sharded) vocab axis, so GSPMD lowers them to
    (B,S)-sized allreduces instead of a (B,S,V) logits all-gather."""
    lg = logits.astype(f32)
    tgt = jnp.maximum(targets, 0)
    lse = jax.nn.logsumexp(lg, axis=-1)
    onehot = jax.nn.one_hot(tgt, lg.shape[-1], dtype=f32)
    label_logit = jnp.sum(lg * onehot, axis=-1)
    ll = label_logit - lse
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(cfg: ModelConfig, pol: ShardingPolicy, params, batch):
    """Next-token CE (+ MoE aux).  batch: tokens (B,S), targets (B,S) with
    -1 = masked."""
    logits, aux = forward(cfg, pol, params, batch)
    targets = batch["targets"]
    mask = (targets >= 0).astype(f32)
    ce = sharded_ce(logits, targets, mask)
    loss = ce + cfg.router_aux_weight * aux
    return loss, {"ce": ce, "aux": aux, "tokens": mask.sum()}


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16, abstract=False):
    """Stacked decode cache (n_blocks leading axis)."""
    _contiguous_only(cfg)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    h, hdm, g, ds, w = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.conv_width

    def mk(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct((cfg.n_blocks,) + shape, dt)
        return jnp.zeros((cfg.n_blocks,) + shape, dt)

    blk = {}
    for j in range(cfg.scan_period):
        if cfg.mixer_kind(j) == "attn":
            blk[f"pos{j}"] = {
                "k": mk((batch, cache_len, kv, hd), dtype),
                "v": mk((batch, cache_len, kv, hd), dtype),
            }
        else:
            blk[f"pos{j}"] = {
                "conv": tuple(
                    mk((batch, w - 1, c), dtype)
                    for c in (cfg.d_inner, g * ds, g * ds)
                ),
                "ssm": mk((batch, h, hdm, ds), f32),
            }
    return blk


def init_paged_cache(cfg: ModelConfig, n_pool_blocks: int, block_size: int, n_slots: int, dtype=jnp.bfloat16,
                     n_shards: int | None = None):
    """Paged decode cache: attention K/V live in a shared block pool
    ``(n_layer_blocks, n_pool_blocks, block_size, kv, hd)`` indexed through
    per-request block tables; SSM/conv state has no sequence axis to page,
    so those leaves keep the per-slot ``(n_layer_blocks, n_slots, ...)``
    layout of ``init_cache``.  The caller reserves one pool index as the
    trash block that unallocated table entries point at.  Latent attention
    (``cfg.mla``) keeps one leaf, ``latent`` ``(..., block_size, 1,
    kv_lora_rank + qk_rope_head_dim)``: the one cached head, whose first
    lanes are also the values.  A config with leading dense layers keeps
    their leaves under ``lead``, beside the periodic stack's.

    ``n_shards``: sharded serving layout — pool leaves gain a leading
    shard axis ``(n_layer_blocks, n_shards, n_pool_blocks, block_size,
    kv, hd)`` (the caller passes the PER-SHARD block count incl. the
    per-shard trash as ``n_pool_blocks`` and lays the shard axis out
    ``P(None, "data", ...)``)."""
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    h, hdm, g, ds, w = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.conv_width
    shard = () if n_shards is None else (n_shards,)

    def position(n, j):
        def mk(shape, dt):
            return jnp.zeros((n,) + shape, dt)

        if cfg.mla:
            return {"latent": mk(shard + (n_pool_blocks, block_size, 1, cfg.latent_dim), dtype)}
        if cfg.mixer_kind(j) == "attn":
            pool_shape = shard + (n_pool_blocks, block_size, kv, hd)
            return {"k": mk(pool_shape, dtype), "v": mk(pool_shape, dtype)}
        return {
            "conv": tuple(
                mk((n_slots, w - 1, c), dtype)
                for c in (cfg.d_inner, g * ds, g * ds)
            ),
            "ssm": mk((n_slots, h, hdm, ds), f32),
        }

    blk = {f"pos{j}": position(cfg.n_blocks, j) for j in range(cfg.scan_period)}
    if cfg.first_dense:
        blk["lead"] = {"pos0": position(cfg.first_dense, 0)}
    return blk


def paged_copy_block(cfg: ModelConfig, cache, src, dst):
    """Copy one pool block across every pool leaf of every layer (K and V,
    or the latent) — the copy-on-write half of prefix sharing.  ``src``
    holds a cached chunk with refcount > 1 (or parked) whose tail the new
    request must overwrite (full-prefix hit ending on a block boundary:
    the last prompt token's K/V write lands in it); the engine allocates
    ``dst`` privately, copies, and repoints the request's table before the
    row's first mixed-dispatch write runs.  Per-slot (SSM/conv) leaves
    have no block axis and pass through untouched.  Sharded pool leaves
    (6-D, see ``init_paged_cache``) copy between GLOBAL ids' (shard,
    local) coordinates — prefix chains are row-affine, so src and dst
    share a shard, but the copy is correct either way."""

    def copy(leaf):
        if leaf.ndim == 6:
            n_local = leaf.shape[2] - 1
            s_src, l_src = src // n_local, src % n_local
            s_dst, l_dst = dst // n_local, dst % n_local
            return leaf.at[:, s_dst, l_dst].set(leaf[:, s_src, l_src])
        return leaf.at[:, dst].set(jnp.take(leaf, src, axis=1))

    def walk(node):
        if "conv" in node or "ssm" in node:  # per-slot state: no blocks
            return node
        return {k: walk(v) if isinstance(v, dict) else copy(v) for k, v in node.items()}

    return walk(cache)


def mixed_step(cfg: ModelConfig, pol: ShardingPolicy, params, tokens, cache,
               block_tables, q_start, q_len, block_size: int, mesh=None, live=None,
               live_max: int | None = None, counters: bool = False):
    """UNIFIED engine step: one layer-stack pass over a mixed batch of
    prefill chunks and decode rows against the paged cache — the ONE
    dispatch the unified serving path issues per engine step, replacing
    separate prefill / decode calls.

    ``tokens``: ``(B, W)`` — each row carries ``q_len[b]`` live tokens
    starting at absolute position ``q_start[b]`` (a decode row is
    ``q_len == 1``; an idle slot is ``q_len == 0``).  Prefix positions
    below ``q_start`` must already sit in pool blocks reachable through
    ``block_tables``; each layer scatters its fresh K/V into the pool
    BEFORE attending (see ``layers.attn_mixed_paged``), so prompts may
    be chunked across steps at any boundary.  Returns ``(logits
    (B, W, V), cache)`` — the caller reads row ``b``'s next token off
    ``logits[b, q_len[b] - 1]`` when its prompt completes this step.

    ``live`` ``(B, W)`` marks the lanes whose output is used (default:
    lanes below ``q_len``); a held-expert layer computes and counts only
    their pairs.  ``live_max``: a bound on the live lanes (the engine's
    token budget), so held experts run on that many gathered lanes.
    ``counters``: also return the routing counters
    (``moe.ROUTING_COUNTERS``, summed over layers) as a third output.
    """
    b, w = tokens.shape
    h = L.embed_apply(cfg, pol, params["embed"], tokens)
    q_start = jnp.asarray(q_start, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)
    positions = q_start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    rows = None
    if cfg.experts_held:
        if live is None:
            live = jnp.arange(w)[None, :] < q_len[:, None]
        if live_max is not None:
            rows = MOE.live_rows(live, live_max)
    h, cache, _, stats = _run_blocks(
        cfg, pol, params, h, positions, mode="mixed", cache=cache,
        paged=(block_tables, block_size, q_len, mesh), live=live, rows=rows,
    )
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.head_apply(cfg, pol, params, h)
    return (logits, cache, _counters(stats)) if counters else (logits, cache)


def _counters(stats):
    return jnp.zeros((len(MOE.ROUTING_COUNTERS),), jnp.int32) if stats is None else stats


def verify_step(cfg: ModelConfig, pol: ShardingPolicy, params, tokens, cache,
                block_tables, q_start, q_len, block_size: int, mesh=None):
    """Speculative draft-k/verify-1 target pass: score ``k + 1`` candidate
    positions per row in ONE dispatch.

    This is ``mixed_step`` run over VERIFY descriptors — each speculating
    row ``b`` carries ``(slot=b, q_start=committed position, q_len=k+1,
    kv_len=q_start+q_len)``: lane 0 is the row's last committed token,
    lanes 1..k the drafter's proposals.  Because each layer scatters the
    lane K/V into the pool BEFORE attending (write-then-attend), lane
    ``j``'s logits equal exactly what a plain 1-token decode would
    produce after emitting lanes ``< j`` — so per-lane argmaxes feed the
    engine's greedy accept-prefix and outputs stay bit-identical to
    non-speculative decode.  Rejected lanes need no device rollback: the
    engine only advances its committed position by the accepted run, and
    the next verify window re-writes every stale position before any
    lane can attend to it.  Rows with ``q_len == 1`` degenerate to plain
    decode lanes; ``q_len == 0`` rows are inert (K/V to the trash
    block).  Returns ``(logits (B, W, V), cache)``."""
    return mixed_step(
        cfg, pol, params, tokens, cache, block_tables, q_start, q_len, block_size,
        mesh=mesh,
    )


def _contiguous_only(cfg: ModelConfig) -> None:
    if cfg.mla or cfg.first_dense:
        raise NotImplementedError(
            f"{cfg.name}: latent attention and leading dense layers have a paged cache only "
            "(init_paged_cache)")


def cache_pspecs(cfg: ModelConfig, pol: ShardingPolicy):
    """PartitionSpec tree matching init_cache structure."""
    _contiguous_only(cfg)
    blk = {}
    for j in range(cfg.scan_period):
        if cfg.mixer_kind(j) == "attn":
            kv_spec = pol.spec(None, "cache_batch", "cache_seq", "cache_kv", None)
            blk[f"pos{j}"] = {"k": kv_spec, "v": kv_spec}
        else:
            blk[f"pos{j}"] = {
                "conv": tuple(
                    pol.spec(None, "cache_batch", None, "act_ff" if i == 0 else None)
                    for i in range(3)
                ),
                "ssm": pol.spec(None, "cache_batch", "act_heads", None, None),
            }
    return blk


def prefill(cfg: ModelConfig, pol: ShardingPolicy, params, batch, cache_len: int | None = None):
    """Process a prompt, build the decode cache.  Returns (logits, cache)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache_len = cache_len or s
    h = _embed_inputs(cfg, pol, params, batch)
    positions = jnp.broadcast_to(jnp.arange(s), tokens.shape)
    cache = init_cache(cfg, b, cache_len, dtype=jnp.dtype(cfg.dtype))
    h, cache, _, _ = _run_blocks(cfg, pol, params, h, positions, mode="prefill", cache=cache)
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return L.head_apply(cfg, pol, params, h), cache


def decode_step(cfg: ModelConfig, pol: ShardingPolicy, params, cache, tokens, pos,
                block_tables=None, block_size: int = 0, mesh=None, live=None,
                counters: bool = False):
    """One decode step.  tokens: (B,1) int32; pos: scalar int32 write
    position (attention sees [0..pos]) or (B,) per-row positions for
    ragged batches.  With ``block_tables`` (``(B, n_max_blocks)`` int32,
    requires per-row ``pos`` and a paged cache from ``init_paged_cache``)
    attention K/V reads/writes go through the block table instead of a
    contiguous per-row stripe.  Returns (logits (B,1,V), cache).

    ``live`` ``(B,)`` marks the rows whose token is used (default: all);
    ``counters`` returns the routing counters too, as in ``mixed_step``."""
    h = L.embed_apply(cfg, pol, params["embed"], tokens)
    pos = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(pos[:, None] if pos.ndim == 1 else pos, tokens.shape)
    paged = None if block_tables is None else (block_tables, block_size, mesh)
    h, cache, _, stats = _run_blocks(
        cfg, pol, params, h, positions, mode="decode", cache=cache, pos=pos, paged=paged,
        live=None if live is None else live[:, None],
    )
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.head_apply(cfg, pol, params, h)
    return (logits, cache, _counters(stats)) if counters else (logits, cache)


def generate(cfg, pol, params, batch, n_tokens: int, temperature: float = 0.0, key=None):
    """Greedy/sampled autoregressive generation (example drivers + e2e QA)."""
    logits, cache = prefill(cfg, pol, params, batch, cache_len=batch["tokens"].shape[1] + n_tokens)
    b = batch["tokens"].shape[0]
    prompt_len = batch["tokens"].shape[1]
    last = logits[:, -1, :]

    def pick(lg, k):
        if temperature <= 0.0:
            return jnp.argmax(lg, -1).astype(jnp.int32)
        return jax.random.categorical(k, lg / temperature).astype(jnp.int32)

    keys = jax.random.split(key if key is not None else jax.random.PRNGKey(0), n_tokens)
    tok = pick(last, keys[0])
    out = [tok]
    for t in range(1, n_tokens):
        logits, cache = decode_step(cfg, pol, params, cache, tok[:, None], prompt_len + t - 1)
        tok = pick(logits[:, -1, :], keys[t])
        out.append(tok)
    return jnp.stack(out, axis=1)  # (B, n_tokens)
