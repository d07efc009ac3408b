"""In-mesh federated retrieval: the device-level realization of Alg. 1
steps 2-4 when providers are mesh slices (DESIGN.md §3 table).

The corpus is sharded over the provider axis (= `data`); each shard runs
local MIPS top-k (Pallas kernel on TPU), then ONLY the (score, global_id)
candidate tuples — k values per query per provider, never raw chunks —
cross the shard boundary via all_gather, exactly mirroring the paper's
"providers return m candidates, orchestrator merges" flow.  A quorum mask
zeroes out failed/straggling providers at the combine, so serving degrades
gracefully (k_n <= k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.retrieval_topk.ref import retrieval_topk_ref


def local_topk(q_emb, corpus_shard, m, use_pallas: bool = False):
    if use_pallas:
        from repro.kernels.retrieval_topk.kernel import retrieval_topk_pallas

        return retrieval_topk_pallas(q_emb, corpus_shard, m)
    return retrieval_topk_ref(q_emb, corpus_shard, m)


def federated_topk(
    q_emb: jax.Array,  # (Q, D) replicated
    corpus: jax.Array,  # (N_total, D) sharded over the provider axis
    m_local: int,
    n_global: int,
    mesh: Mesh | None = None,
    provider_axis: str = "data",
    alive: jax.Array | None = None,  # (n_providers,) bool quorum mask
    use_pallas: bool = False,
):
    """Returns (scores (Q, n_global), global_idx (Q, n_global), provider (Q, n_global))."""
    if mesh is None or provider_axis not in getattr(mesh, "shape", {}):
        s, i = local_topk(q_emb, corpus, n_global, use_pallas)
        return s, i, jnp.zeros_like(i)

    n_prov = mesh.shape[provider_axis]
    n_total = corpus.shape[0]
    n_loc = n_total // n_prov
    if alive is None:
        alive = jnp.ones((n_prov,), bool)

    def shard_fn(q, c_loc, alive_):
        pid = jax.lax.axis_index(provider_axis)
        s, i = local_topk(q, c_loc, m_local, use_pallas)  # (Q, m) local ids
        s = jnp.where(alive_[pid], s, -jnp.inf)  # straggler/failure mask
        gid = i + pid * n_loc
        # only (score, id) tuples cross the provider boundary:
        s_all = jax.lax.all_gather(s, provider_axis, axis=0)  # (P, Q, m)
        g_all = jax.lax.all_gather(gid, provider_axis, axis=0)
        p_all = jax.lax.all_gather(jnp.full_like(gid, pid), provider_axis, axis=0)
        q_n = q.shape[0]
        s_flat = s_all.transpose(1, 0, 2).reshape(q_n, -1)
        g_flat = g_all.transpose(1, 0, 2).reshape(q_n, -1)
        p_flat = p_all.transpose(1, 0, 2).reshape(q_n, -1)
        top_s, pos = jax.lax.top_k(s_flat, n_global)
        top_g = jnp.take_along_axis(g_flat, pos, axis=-1)
        top_p = jnp.take_along_axis(p_flat, pos, axis=-1)
        return top_s, top_g, top_p

    other_axes = [a for a in mesh.axis_names if a != provider_axis]
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(provider_axis, None), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fn(q_emb, corpus, alive)


@functools.partial(jax.jit, static_argnames=("m_local", "n_global", "provider_axis", "use_pallas"))
def federated_topk_jit(q_emb, corpus, m_local, n_global, mesh=None, provider_axis="data", alive=None, use_pallas=False):
    return federated_topk(q_emb, corpus, m_local, n_global, mesh, provider_axis, alive, use_pallas)
