"""Expert-parallel Mixture-of-Experts (top-k routing, GQA-era configs).

Production path = ``masked-local EP``: tokens stay sharded over the data
axis and replicated over `model`; each model shard owns E/tp experts,
compacts the (token, expert) pairs routed to *its* experts into a fixed
capacity buffer, runs a grouped matmul (``jax.lax.ragged_dot``), scatters
back, and a single psum over `model` combines expert outputs — the same
collective a Megatron row-parallel MLP already pays.  This handles every
shape cell including decode (tokens-per-device < 1 regimes) and was
validated exactly against the dense reference (tests/test_moe.py).

An all-to-all token-resharded variant (lower collective bytes for large
T) is implemented as ``moe_apply_a2a`` — see EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec
from repro.models.layers import mlp_specs, mlp_apply
from repro.runtime.sharding import ShardingPolicy


def padded_experts(cfg: ModelConfig, tp: int) -> int:
    return int(math.ceil(cfg.n_experts / tp) * tp)


def moe_specs(cfg: ModelConfig, tp_hint: int = 16) -> dict:
    d, f = cfg.d_model, cfg.resolved_moe_d_ff
    e_pad = padded_experts(cfg, tp_hint)
    s = {
        "router": ParamSpec((d, e_pad), ("embed", "experts"), "fan_in", fan_in_dims=(0,)),
        "wg": ParamSpec((e_pad, d, f), ("experts", "expert_in", "expert_mlp"), "fan_in", fan_in_dims=(1,)),
        "wu": ParamSpec((e_pad, d, f), ("experts", "expert_in", "expert_mlp"), "fan_in", fan_in_dims=(1,)),
        "wd": ParamSpec((e_pad, f, d), ("experts", "expert_mlp", "expert_in"), "fan_in", fan_in_dims=(1,)),
    }
    if cfg.n_shared_experts:
        s["shared"] = mlp_specs(cfg, d_ff=cfg.n_shared_experts * f)
        s["shared_gate"] = ParamSpec((d, 1), ("embed", None), "fan_in", fan_in_dims=(0,))
    return s


def _route(cfg: ModelConfig, router_w, x2d):
    """Top-k routing in f32.  x2d: (T, d) -> gates (T,k), ids (T,k), probs (T,E_pad)."""
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    e_pad = logits.shape[-1]
    valid = jnp.arange(e_pad) < cfg.n_experts
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.moe_top_k)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)  # renormalize
    return gates, ids, probs


def _aux_loss(cfg: ModelConfig, probs, ids):
    """Switch-style load-balance loss (computed over local tokens; callers
    psum/mean across shards)."""
    e = probs.shape[-1]
    me = probs.mean(axis=0)  # (E,)
    ce = jnp.zeros((e,), jnp.float32)
    ce = ce.at[ids.reshape(-1)].add(1.0)
    ce = ce / jnp.clip(ce.sum(), 1.0)
    return e * jnp.sum(me * ce)


def _expert_compute(wg, wu, wd, xbuf, group_sizes):
    """SwiGLU grouped matmul over capacity buffer (CAP, d)."""
    dt = xbuf.dtype
    h = jax.nn.silu(jax.lax.ragged_dot(xbuf, wg.astype(dt), group_sizes)) * jax.lax.ragged_dot(
        xbuf, wu.astype(dt), group_sizes
    )
    return jax.lax.ragged_dot(h, wd.astype(dt), group_sizes)


def _local_moe(cfg: ModelConfig, cap: int, axis_names: tuple, p, x_loc):
    """Per-device body under shard_map.  x_loc: (T_loc, d) replicated over
    `model`; p["wg"/"wu"/"wd"] are the local expert shards (E_loc, ...)."""
    tp = jax.lax.axis_size("model")
    my = jax.lax.axis_index("model")
    e_loc = p["wg"].shape[0]
    t_loc = x_loc.shape[0]

    gates, ids, probs = _route(cfg, p["router"], x_loc)
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(t_loc), cfg.moe_top_k)
    mine = (flat_ids // e_loc) == my
    eloc = jnp.where(mine, flat_ids % e_loc, e_loc)  # e_loc == pad bucket
    order = jnp.argsort(eloc)[:cap]
    sel_e = eloc[order]
    sel_t = tok_idx[order]
    sel_g = jnp.where(sel_e < e_loc, flat_gates[order], 0.0)
    xbuf = x_loc[sel_t]
    gs = jnp.bincount(jnp.clip(sel_e, 0, e_loc), length=e_loc + 1)[:e_loc].astype(jnp.int32)

    y = _expert_compute(p["wg"], p["wu"], p["wd"], xbuf, gs)
    out = jnp.zeros_like(x_loc).at[sel_t].add(
        (y * sel_g[:, None].astype(y.dtype)).astype(x_loc.dtype)
    )
    out = jax.lax.psum(out, "model")
    aux = jax.lax.pmean(_aux_loss(cfg, probs, ids), axis_names)
    return out, aux


def moe_apply(cfg: ModelConfig, pol: ShardingPolicy, p, x):
    """x: (B, S, d) -> (out, aux_loss).  Sharded path uses shard_map over the
    full mesh; 1-device path runs the same body inline (tp=1)."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    mesh = pol.mesh
    if (
        cfg.moe_impl == "a2a"
        and mesh is not None
        and "model" in mesh.shape
        and mesh.size > 1
        and (b * s) % mesh.size == 0
    ):
        return moe_apply_a2a(cfg, pol, p, x)
    if mesh is not None and "model" in mesh.shape and mesh.size > 1:
        tp = mesh.shape["model"]
        dp = mesh.size // tp
        batch_rule = pol.rules.get("act_batch")
        t_loc = max(b * s // dp, 1) if batch_rule else b * s
        cap = _capacity(cfg, t_loc, tp)
        tok_axes = batch_rule if batch_rule else None
        tok_spec = P(tok_axes, None)
        axis_names = tuple(mesh.axis_names)
        out, aux = jax.shard_map(
            partial(_local_moe, cfg, cap, axis_names),
            mesh=mesh,
            in_specs=(_moe_param_specs(p), tok_spec),
            out_specs=(tok_spec, P()),
            check_vma=False,
        )(p, x2d)
    else:
        cap = _capacity(cfg, b * s, 1)
        out, aux = _local_moe_single(cfg, cap, p, x2d)
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        shared = mlp_apply(cfg, pol, p["shared"], x)
        gate = jax.nn.sigmoid((x @ p["shared_gate"].astype(x.dtype)).astype(jnp.float32))
        out = out + shared * gate.astype(x.dtype)
    return pol.shard(out, "act_batch", "act_seq", "act_embed"), aux


def _local_moe_single(cfg, cap, p, x2d):
    """tp=1 path without shard_map (smoke tests / CPU)."""
    t = x2d.shape[0]
    e_pad = p["router"].shape[-1]
    gates, ids, probs = _route(cfg, p["router"], x2d)
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(t), cfg.moe_top_k)
    order = jnp.argsort(flat_ids)[:cap]
    sel_e = flat_ids[order]
    sel_t = tok_idx[order]
    sel_g = flat_gates[order]
    xbuf = x2d[sel_t]
    gs = jnp.bincount(sel_e, length=e_pad).astype(jnp.int32)
    y = _expert_compute(p["wg"], p["wu"], p["wd"], xbuf, gs)
    out = jnp.zeros_like(x2d).at[sel_t].add((y * sel_g[:, None].astype(y.dtype)).astype(x2d.dtype))
    return out, _aux_loss(cfg, probs, ids)


def _capacity(cfg: ModelConfig, t_loc: int, tp: int) -> int:
    cap = int(math.ceil(t_loc * cfg.moe_top_k / tp * cfg.capacity_slack))
    cap = max(cap, cfg.moe_top_k)
    return int(math.ceil(cap / 8) * 8)


def _moe_param_specs(p):
    """shard_map in_specs for the expert params: experts over `model`."""
    specs = {}
    for k, v in p.items():
        if k in ("wg", "wu", "wd"):
            specs[k] = P("model", *([None] * (v.ndim - 1)))
        elif k == "shared":
            specs[k] = jax.tree.map(lambda _: P(), v)
        else:
            specs[k] = P(*([None] * v.ndim))
    return specs


# ------------------------------------------------------------------ #
# all-to-all expert parallelism (the optimized train-shape variant)
# ------------------------------------------------------------------ #


def _local_moe_a2a(cfg: ModelConfig, cap: int, axis_names: tuple, p, x_loc):
    """Tokens sharded over (data x model); each device routes its T_loc2
    tokens, ships them to their expert shard via all_to_all, computes the
    grouped matmul, and ships results back.  Collective bytes per device:
    2 x cap x tp x d x 2B (there + back, bf16) vs the psum variant's
    2 x T_loc x d per direction — a ~tp/(2k·slack) reduction
    (EXPERIMENTS.md §Perf cell B)."""
    tp = jax.lax.axis_size("model")
    my = jax.lax.axis_index("model")
    e_loc = p["wg"].shape[0]
    t_loc = x_loc.shape[0]

    gates, ids, probs = _route(cfg, p["router"], x_loc)
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(t_loc), cfg.moe_top_k)
    dest = flat_ids // e_loc  # destination shard per (token, k) pair

    # slot each pair into its destination bucket (capacity `cap` per dest)
    order = jnp.argsort(dest)  # pairs grouped by dest
    d_sorted = dest[order]
    # position within the destination group
    pos_in_dest = jnp.arange(d_sorted.size) - jnp.searchsorted(d_sorted, d_sorted, side="left")
    keep = pos_in_dest < cap
    slot = jnp.where(keep, d_sorted * cap + pos_in_dest, tp * cap)  # overflow -> dropped

    send_x = jnp.zeros((tp * cap + 1, x_loc.shape[1]), x_loc.dtype).at[slot].set(x_loc[tok_idx[order]])[:-1]
    send_e = jnp.full((tp * cap + 1,), e_loc, jnp.int32).at[slot].set(
        jnp.where(keep, flat_ids[order] % e_loc, e_loc)
    )[:-1]
    send_g = jnp.zeros((tp * cap + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, flat_gates[order], 0.0)
    )[:-1]
    send_t = jnp.zeros((tp * cap + 1,), jnp.int32).at[slot].set(tok_idx[order])[:-1]

    # ship token payloads to their expert shard
    recv_x = jax.lax.all_to_all(send_x.reshape(tp, cap, -1), "model", 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e.reshape(tp, cap), "model", 0, 0, tiled=False)
    recv_x = recv_x.reshape(tp * cap, -1)
    recv_e = recv_e.reshape(tp * cap)

    # grouped matmul over the local experts (sorted by local expert id)
    eorder = jnp.argsort(recv_e)
    xbuf = recv_x[eorder]
    gs = jnp.bincount(jnp.clip(recv_e, 0, e_loc), length=e_loc + 1)[:e_loc].astype(jnp.int32)
    y = _expert_compute(p["wg"], p["wu"], p["wd"], xbuf, gs)
    y = jnp.zeros_like(y).at[eorder].set(y)  # un-sort

    # ship results back and combine
    back = jax.lax.all_to_all(y.reshape(tp, cap, -1), "model", 0, 0, tiled=False)
    back = back.reshape(tp * cap, -1)
    out = jnp.zeros_like(x_loc).at[send_t].add(
        (back * send_g[:, None].astype(back.dtype)).astype(x_loc.dtype)
    )
    aux = jax.lax.pmean(_aux_loss(cfg, probs, ids), axis_names)
    return out, aux


def moe_apply_a2a(cfg: ModelConfig, pol: ShardingPolicy, p, x):
    """all_to_all EP path; requires B*S divisible by dp*tp (train shapes)."""
    b, s, d = x.shape
    mesh = pol.mesh
    assert mesh is not None and "model" in mesh.shape
    tp = mesh.shape["model"]
    dp = mesh.size // tp
    assert (b * s) % (dp * tp) == 0, (b * s, dp, tp)
    t_loc2 = b * s // (dp * tp)
    cap = _capacity(cfg, t_loc2, tp)
    batch_rule = pol.rules.get("act_batch") or ()
    tok_axes = tuple(a for a in (batch_rule if isinstance(batch_rule, tuple) else (batch_rule,)) if a)
    tok_spec = P(tuple(tok_axes) + ("model",) if "model" not in tok_axes else tok_axes, None)
    x2d = x.reshape(b * s, d)
    out, aux = jax.shard_map(
        partial(_local_moe_a2a, cfg, cap, tuple(mesh.axis_names)),
        mesh=mesh,
        in_specs=(_moe_param_specs(p), tok_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(p, x2d)
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        shared = mlp_apply(cfg, pol, p["shared"], x)
        gate = jax.nn.sigmoid((x @ p["shared_gate"].astype(x.dtype)).astype(jnp.float32))
        out = out + shared * gate.astype(x.dtype)
    return pol.shard(out, "act_batch", "act_seq", "act_embed"), aux


# ------------------------------------------------------------------ #
# held experts: one chip's share of an expert-parallel layer, dropless
# ------------------------------------------------------------------ #

# routing counters a held-expert layer returns, summed over layers (and the
# steps of a fused decode chunk): (token, expert) pairs routed to the held
# experts, held experts with at least one pair, and the most pairs any one
# held expert took
ROUTING_COUNTERS = ("moe_pairs_held", "moe_experts_touched", "moe_max_expert_pairs")


def held_moe_specs(cfg: ModelConfig) -> dict:
    """The router at its full width (``n_experts``), the selection bias,
    the ``experts_held`` experts this layer computes, and the shared
    experts as one un-gated MLP."""
    d, f, e = cfg.d_model, cfg.resolved_moe_d_ff, cfg.experts_held
    s = {
        "router": ParamSpec((d, cfg.n_experts), ("embed", None), "fan_in", fan_in_dims=(0,)),
        "wg": ParamSpec((e, d, f), (None, "expert_in", "expert_mlp"), "fan_in", fan_in_dims=(1,)),
        "wu": ParamSpec((e, d, f), (None, "expert_in", "expert_mlp"), "fan_in", fan_in_dims=(1,)),
        "wd": ParamSpec((e, f, d), (None, "expert_mlp", "expert_in"), "fan_in", fan_in_dims=(1,)),
        "select_bias": ParamSpec((cfg.n_experts,), (None,), "zeros"),
    }
    if cfg.n_shared_experts:
        s["shared"] = mlp_specs(cfg, d_ff=cfg.n_shared_experts * f)
    return s


def route(cfg: ModelConfig, p, x2d):
    """DeepSeek-V3 routing in float32 over all ``n_experts``: sigmoid
    scores; the top ``moe_top_k`` picked by score plus the selection bias,
    weighted by the unbiased scores, normalised, times ``moe_scale``.
    x2d: (T, d) -> (weights (T, k) float32, ids (T, k))."""
    f32 = jnp.float32
    logits = jnp.dot(x2d.astype(f32), p["router"].astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + p["select_bias"].astype(f32), cfg.moe_top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.moe_scale, ids


def live_rows(live, live_max: int):
    """``live_max`` token indices into the (B, S) bool ``live`` that hold
    every live token, the live ones first (at most ``live_max`` are live);
    None where ``live_max`` covers every token.  A step computes them once
    for all its layers."""
    t = live.size
    if live_max >= t:
        return None
    return jnp.argsort(~live.reshape(t), stable=True)[:live_max]


def held_moe_apply(cfg: ModelConfig, pol: ShardingPolicy, p, x, live=None, rows=None):
    """One chip's share of an expert-parallel MoE layer: the router runs
    over all experts; this layer computes only the part of the experts it
    holds, ``[expert_offset, expert_offset + experts_held)``, with no
    capacity (dropless: every token is computed by every held expert and
    weighted by its routing weight, zero for an expert it did not pick,
    so a token's output never depends on the other tokens of its step),
    plus the shared experts.  The absent experts' part is left out, as
    their chips would add it.

    The held experts run as one batched matmul over the experts, in XLA:
    ``jax.lax.ragged_dot`` lowers on the TPU to Mosaic kernels, which
    would sit beside the attention kernel in each step program.

    x: (B, S, d); ``live`` (B, S) bool marks the tokens whose output is
    used (None: all): other tokens are neither computed by the held
    experts nor counted.  ``rows``: indices into the ``B * S`` tokens that
    hold every live one (``live_rows``), so the held experts run on those
    gathered tokens instead of all of them.  Returns (out (B, S, d),
    counters int32[3] (``ROUTING_COUNTERS``))."""
    b, s, d = x.shape
    e, t = cfg.experts_held, b * s
    x2d = x.reshape(t, d)
    w, ids = route(cfg, p, x2d)
    local = ids - cfg.expert_offset
    held = (local >= 0) & (local < e)
    if live is not None:
        held = held & live.reshape(t, 1)
    # (T, e) routing weight of each token for each held expert
    gate = jnp.sum(jnp.where(held[..., None], w[..., None], 0.0)
                   * (local[..., None] == jnp.arange(e)), axis=1)
    xs, gs = (x2d, gate) if rows is None else (x2d[rows], gate[rows])
    dt = x.dtype
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xs, p["wg"].astype(dt))) * jnp.einsum(
        "td,edf->etf", xs, p["wu"].astype(dt))
    y = jnp.einsum("etf,efd->etd", h, p["wd"].astype(dt))
    routed = jnp.einsum("etd,te->td", y.astype(jnp.float32), gs).astype(dt)
    out = routed if rows is None else jnp.zeros_like(x2d).at[rows].set(routed)
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + mlp_apply(cfg, pol, p["shared"], x)
    counts = jnp.sum(held[..., None] & (local[..., None] == jnp.arange(e)), axis=(0, 1))
    stats = jnp.stack([counts.sum(), (counts > 0).sum(), counts.max()]).astype(jnp.int32)
    return pol.shard(out, "act_batch", "act_seq", "act_embed"), stats


# ------------------------------------------------------------------ #
# dense reference (oracle for tests)
# ------------------------------------------------------------------ #


def moe_reference(cfg: ModelConfig, p, x):
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    gates, ids, probs = _route(cfg, p["router"], x2d)
    out = jnp.zeros_like(x2d)
    for e in range(cfg.n_experts):
        w = jnp.where(ids == e, gates, 0.0).sum(-1)  # (T,)
        dt = x2d.dtype
        h = jax.nn.silu(x2d @ p["wg"][e].astype(dt)) * (x2d @ p["wu"][e].astype(dt))
        y = h @ p["wd"][e].astype(dt)
        out = out + y * w[:, None].astype(dt)
    return out.reshape(b, s, d), _aux_loss(cfg, probs, ids)
