"""RAG serving engine: a resident, multi-tenant continuous-batching core
over a slot pool with a contiguous- or paged-KV cache.

Request flow (paper Fig. 2/3 in serving form):
  query -> federated retrieval (core.retrieval / orchestrator)
        -> enclave re-rank -> prompt build -> slot prefill -> decode chunks

Serving modes (all share the slot-state contract):

  * **Lock-step** (``step_batch``): drain the queue in fixed ``max_batch``
    chunks, one packed prefill + one fused decode ``while_loop`` per
    chunk.  Kept as the deterministic baseline the continuous path is
    parity-tested (and benchmarked) against.  Always contiguous.
  * **Continuous, contiguous** (``serve_stream`` with ``paged=False``): a
    fixed pool of ``max_batch`` decode slots over per-slot cache stripes.
    Finished rows (EOS or per-request budget) retire and free their slot;
    the ``Scheduler`` admits queued requests into free slots — bucketed
    into power-of-2 groups so ``k`` waiting requests cost ``O(log k)``
    fused prefill+scatter dispatches instead of ``k`` — while the other
    slots keep decoding.  Decode runs in fused chunks of at most
    ``sched_chunk`` steps with ONE host sync per chunk.  This path is the
    second parity oracle next to lock-step.
  * **Continuous, paged** (``paged=True``): ALWAYS the **unified chunked
    prefill** loop (``_serve_unified``).  Every engine step issues ONE
    ``_mixed_rows`` call over per-row ``(q_start, q_len)`` descriptors —
    prompt tokens are chunked across steps (at most ``token_budget``
    query lanes per step, shared with the 1-lane decode rows), so a long
    prompt arrival never stalls in-flight decodes behind a monolithic
    prefill, and the dispatch count per step is O(1) regardless of how
    many requests are admitting.  The kernel underneath
    (``kernels/chunked_prefill``) reads prefix K/V straight from the
    block pool, so the prefix cache works with ``attn_impl="pallas"``,
    prompts longer than ``attn_chunk``, and non-f32 caches — cold and
    warm rows both attend through the pool, making hit-vs-miss parity
    structural.  (The legacy dense+suffix admission pipeline and its
    dependency-wave machinery were retired once this path reached
    bit-parity everywhere; the lock-step and contiguous engines are the
    surviving oracles.)

**Resident state.**  A paged engine is a long-lived service: the device
cache, ``BlockPool``, per-slot ``BlockTable``s, and the ``PrefixIndex``
are created lazily on first use and survive across ``serve`` /
``serve_stream`` calls, so a repeated system preamble is a prefix HIT on
the second call — no re-prefill.  ``reset_cache()`` drops everything for
an explicitly cold start.  With ``ServeConfig.spill_bytes`` set the
prefix cache is **tiered**: parked chains evicted under pool pressure
*demote* their K/V to a bounded host-RAM ``HostBlockStore`` and come
back via ``device_put`` + table repoint instead of re-prefill (see
``serving/kv_cache``).

**Tenants.**  Admission order is the scheduler's: per-tenant SLO classes
(priority preempts the *queue*, weighted-fair within a class, FIFO
within a tenant).  The engine never preempts a running slot — an
admitted request decodes to EOS/budget/OOM on its own terms — and
reports per-tenant admission + prefix gauges back through
``Scheduler.record_tenant_admit``.

Degradation contract (terminal, flagged, neighbors unharmed):
  * ``truncated`` — force-retired on KV-pool OOM at a growth boundary;
    the answer is a prefix of what the budget allowed.
  * ``deadlocked`` — force-retired empty when an admission waits on
    cached chunks no in-flight fill will materialize
    (``AdmissionDeadlock`` from the ``pending_blocks`` resolver;
    unreachable with commit-ordered deps, but degrading beats wedging).
  * ``expired`` — dropped by the scheduler at its admission deadline.
  * ``degraded`` (pipeline-level, ``core/pipeline``) — a federation
    round that missed quorum; the serving layers above still answer.

Cache layouts (``ServeConfig.paged`` selects; bit-identical for the
same admission order):

  * **Contiguous** (default): every cache leaf is ``(n_layer_blocks, B,
    cache_len, ...)`` — one ``max_prompt_len + max_new_tokens`` stripe
    per slot.  Simple, but a short query pays worst-case HBM and
    ``max_batch`` is pinned to physical stripes.
  * **Paged** (``paged=True``): attention K/V live in one shared pool of
    ``n_pool_blocks`` fixed-size token blocks — leaves ``(n_layer_blocks,
    n_pool_blocks + 1, block_size, kv, hd)`` (the ``+1`` is a trash block
    that unallocated table entries point at) — indexed through per-slot
    block tables ``(B, cache_len_padded / block_size)``.  A
    ``serving/kv_cache.BlockPool`` allocates blocks at admission
    (``ceil(prompt_len / block_size)``), grows tables incrementally at
    decode-chunk boundaries, and frees them at retire.  Admission is
    memory-aware: a request is only popped while free blocks cover its
    prompt + first decode token, so ``max_batch`` slots can exceed the
    contiguous stripe count for short-prompt traffic at the same HBM; a
    request that cannot get a block at a chunk boundary is force-retired
    with what it already emitted (its neighbors are never corrupted).
    Requires an all-attention model (SSM/conv state folds the whole
    sequence and cannot resume a chunked prompt).

Both paths pack prompts left-aligned (PAD tail) and decode each row from
its OWN cache position (per-row ``lengths``), so ragged batches never
attend to PAD key/values; rows that hit EOS are masked to PAD for the
rest of their stay in the batch (post-EOS logits are never emitted).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.data.tokenizer import EOS, PAD
from repro.models import lm as LM
from repro.models import moe as MOE
from repro.runtime import tracing
from repro.runtime.sharding import ShardingPolicy
from repro.serving.kv_cache import (
    BlockPool,
    BlockTable,
    HostBlockStore,
    PrefixIndex,
    blocks_for,
)
from repro.serving.scheduler import Request, Scheduler


class AdmissionDeadlock(RuntimeError):
    """Prefix-cache admission dependency resolution stalled: some admitted
    rows wait on cached chunks that no in-flight fill is going to
    materialize.  With deps derived from ``PrefixIndex.commit`` order this
    is unreachable (an admit can only depend on chunks registered by an
    EARLIER admit, so the wait graph is acyclic), but a hang here would
    wedge the whole serve loop — so instead of asserting, the resolver
    raises with whatever DID resolve plus the stuck slots, and the engine
    force-retires the latter with an empty, ``deadlocked``-flagged
    result."""

    def __init__(self, waves: list, stuck: list):
        super().__init__(
            f"admission dependency resolution stalled: {len(stuck)} row(s) wait "
            f"on cached chunks no in-flight fill writes (cyclic prefix deps?)"
        )
        self.waves = waves
        self.stuck = stuck


def resolve_fill_deps(fill_deps: dict[int, frozenset], pending) -> list[int]:
    """Runnable in-flight fills given the ``pending_blocks`` key set.

    ``fill_deps`` maps slot -> the cached-chunk blocks its shared chain /
    COW source reads; ``pending`` is the set of blocks some in-flight
    fill has registered but not yet materialized.  A fill is runnable
    once none of its deps are still pending.  Raises
    :class:`AdmissionDeadlock` (carrying the stuck slots) when fills
    exist but none can run — the engine's cue to force-retire them as
    ``deadlocked`` instead of spinning forever."""
    pending = set(pending)
    runnable = [i for i, deps in sorted(fill_deps.items()) if not (deps & pending)]
    if fill_deps and not runnable:
        raise AdmissionDeadlock([], sorted(fill_deps))
    return runnable


def _stamp_first_tokens(slots, emitted, fills=None) -> None:
    """Set ``first_token_at`` on each request whose first answer token the
    read-back just brought (``emitted`` reached 1).  A row whose prompt is
    still streaming (``fills``) holds a stale count and is skipped."""
    now = None
    for i, req in enumerate(slots):
        if (req is not None and req.first_token_at is None and emitted[i] >= 1
                and (fills is None or fills[i] is None)):
            now = now or time.monotonic()
            req.first_token_at = now


def accept_prefix(draft, target, *, q_len=None, rem=None, done=None, eos=EOS):
    """Greedy draft-k/verify-1 acceptance: per row, the committed run is
    the longest common prefix of ``draft`` and the target's per-lane
    argmaxes PLUS exactly one target-sourced correction token.

    ``draft``: ``(B, k)`` drafter proposals; ``target``: ``(B, k + 1)``
    target argmaxes where lane ``j`` is the target's next token after the
    row has emitted ``target[:j]`` (valid only while ``draft[:j] ==
    target[:j]`` — the causal verify dispatch guarantees this).  Lane
    ``j`` commits iff every draft before it matched, no earlier
    committed lane was EOS (plain decode stops after emitting EOS), and
    the optional clips hold: ``q_len`` (live verify lanes this round),
    ``rem`` (per-row remaining token budget), ``done``.  All clip masks
    are prefix-monotone, so the committed lanes are a contiguous run
    ``target[:n_emit]`` — bit-identical to what plain greedy decode
    would emit one token at a time.

    Returns ``(n_emit, can_emit)``: committed token count ``(B,)`` and
    the per-lane commit mask ``(B, k + 1)``."""
    d = jnp.asarray(draft)
    t = jnp.asarray(target)
    b, k = d.shape
    j = jnp.arange(k + 1)
    one = jnp.ones((b, 1), jnp.int32)
    ok = jnp.cumprod(
        jnp.concatenate([one, (d == t[:, :k]).astype(jnp.int32)], axis=1), axis=1
    ).astype(bool)
    no_eos = jnp.cumprod(
        jnp.concatenate([one, (t[:, :k] != eos).astype(jnp.int32)], axis=1), axis=1
    ).astype(bool)
    can = ok & no_eos
    if q_len is not None:
        can = can & (j[None, :] < jnp.asarray(q_len)[:, None])
    if rem is not None:
        can = can & (j[None, :] < jnp.asarray(rem)[:, None])
    if done is not None:
        can = can & ~jnp.asarray(done)[:, None]
    return can.sum(axis=1).astype(jnp.int32), can


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8  # decode slots (continuous) / chunk size (lock-step)
    max_prompt_len: int = 512
    max_new_tokens: int = 16  # hard cap; per-request budgets clamp to this
    temperature: float = 0.0
    sched_chunk: int = 8  # max fused decode steps between scheduler runs
    paged: bool = False  # paged KV cache (block pool) vs contiguous stripes
    block_size: int = 32  # tokens per KV block (paged mode)
    # pool size in blocks; None -> the HBM of max_batch contiguous stripes,
    # so paged-vs-contiguous comparisons at the default are equal-memory
    n_pool_blocks: int | None = None
    # refcounted prefix cache on the paged pool: admission looks up the
    # longest cached prompt prefix (block-granular hash-chain), shares
    # those blocks into the new request's table, and prefills only the
    # suffix; retired prompt blocks park in an LRU index for reuse.  The
    # index is RESIDENT: it survives across serve calls on this engine
    prefix_cache: bool = False
    # unified chunked prefill query-lane cap per engine step (paged-only;
    # paged engines always run the unified mixed-dispatch loop).  None
    # defaults to max_prompt_len — i.e. a whole prompt may prefill in one
    # step; smaller budgets chunk prompts across steps so arrivals never
    # stall in-flight decodes
    token_budget: int | None = None
    # host-RAM spill tier for the prefix cache, in bytes (requires
    # prefix_cache): parked chains evicted under pool pressure demote
    # their K/V to host memory and re-admit by upload instead of
    # re-prefill.  None disables tiering (eviction discards)
    spill_bytes: int | None = None
    # speculative decoding (draft-k / verify-1, paged-only): a resident
    # drafter model proposes ``draft_k`` greedy tokens per decode slot
    # each round; the target model scores all ``draft_k + 1`` positions
    # in its ONE mixed dispatch (each speculating row becomes a
    # ``(slot, q_start, q_len=k+1, kv_len)`` verify descriptor) and
    # commits the longest matching prefix plus one corrected token.
    # Greedy accept-prefix keeps outputs BIT-identical to plain decode;
    # 0 disables speculation entirely (the engine runs today's path
    # byte-for-byte)
    draft_k: int = 0
    # drafter architecture + params.  None defaults to the target model
    # (self-speculation — useful for parity tests; every draft accepted).
    # A real deployment points these at a small config (e.g.
    # ``configs/smollm_360m``) sharing the target's vocab
    draft_config: ModelConfig | None = None
    draft_params: object | None = None
    # sharded paged serving (paged-only): partition the KV block pool
    # over ``shards`` devices on a "data" mesh axis — pool leaves become
    # ``(n_layer_blocks, shards, n_pool_blocks/shards + 1, bs, kv, hd)``
    # laid out ``P(None, "data", ...)`` and every engine step runs the
    # DISTRIBUTED mixed dispatch (per-shard scatter + partials, merged by
    # ``dist_decode.combine_partials``).  Allocation is row-affine (a
    # request's whole chain on one shard), which makes ``shards=N``
    # bit-identical to ``shards=1`` for the same admission order.
    # ``None`` (default) keeps the single-device unsharded path
    # byte-for-byte; note ``shards=1`` runs the sharded machinery (the
    # bitwise reference for N > 1) and differs from ``None`` only by
    # flash-partials reassociation
    shards: int | None = None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, pol: ShardingPolicy, params, scfg: ServeConfig):
        self.cfg, self.pol, self.params, self.scfg = cfg, pol, params, scfg
        cache_len = scfg.max_prompt_len + scfg.max_new_tokens
        self._cache_len = cache_len
        # paged geometry: the logical cache length rounds up to a block
        # multiple so a block table addresses exactly the same number of
        # key positions as a contiguous stripe (bit-parity needs equal
        # lane counts through the masked softmax)
        bs = scfg.block_size
        self._blocks_per_slot = blocks_for(cache_len, bs)
        self._cache_len_padded = self._blocks_per_slot * bs
        if scfg.paged:
            n_pool = (
                scfg.n_pool_blocks
                if scfg.n_pool_blocks is not None
                else scfg.max_batch * self._blocks_per_slot
            )
            if n_pool < self._blocks_per_slot:
                raise ValueError(
                    f"n_pool_blocks={n_pool} cannot hold one max-size request "
                    f"({self._blocks_per_slot} blocks of {bs})"
                )
            self._n_pool_blocks = n_pool
            self._trash_block = n_pool  # extra pool index for masked writes
        # sharded pool geometry + mesh (built once, a closure constant of
        # every jitted step so shard_map never retraces on it)
        self._shards = scfg.shards
        self._mesh = None
        if scfg.shards is not None:
            if not scfg.paged:
                raise ValueError(
                    "shards (sharded paged serving) requires paged=True: only "
                    "the block pool partitions over the mesh"
                )
            if scfg.shards < 1:
                raise ValueError(f"shards={scfg.shards} must be >= 1")
            if self._n_pool_blocks % scfg.shards:
                raise ValueError(
                    f"n_pool_blocks={self._n_pool_blocks} must divide evenly "
                    f"over shards={scfg.shards}"
                )
            self._n_local = self._n_pool_blocks // scfg.shards
            if self._n_local < self._blocks_per_slot:
                raise ValueError(
                    f"per-shard pool ({self._n_local} blocks) cannot hold one "
                    f"max-size request ({self._blocks_per_slot} blocks): "
                    "allocation is row-affine, a request never spans shards"
                )
            devs = jax.devices()
            if len(devs) < scfg.shards:
                raise ValueError(
                    f"shards={scfg.shards} needs that many devices, have "
                    f"{len(devs)} (CPU: set XLA_FLAGS="
                    "--xla_force_host_platform_device_count before importing jax)"
                )
            self._mesh = Mesh(np.array(devs[: scfg.shards]), ("data",))
            # weights are replicated over the mesh ONCE here; left on the
            # default device they would be copied to every shard per step
            self.params = params = jax.device_put(
                params, NamedSharding(self._mesh, PartitionSpec())
            )
        if scfg.prefix_cache and not scfg.paged:
            raise ValueError(
                "prefix_cache=True requires paged=True: block tables are "
                "what make prompt prefixes shareable"
            )
        if scfg.spill_bytes is not None:
            if not scfg.prefix_cache:
                raise ValueError(
                    "spill_bytes (host spill tier) requires prefix_cache=True: "
                    "only cached prefix chains are demotable"
                )
            if scfg.spill_bytes < 1:
                raise ValueError(f"spill_bytes={scfg.spill_bytes} must be >= 1")
        if scfg.token_budget is not None:
            if scfg.token_budget < 1:
                raise ValueError(f"token_budget={scfg.token_budget} must be >= 1")
            if not scfg.paged:
                raise ValueError(
                    "token_budget (unified chunked prefill) requires "
                    "paged=True: mixed dispatches read and write K/V "
                    "through the shared block pool"
                )
        if scfg.paged and any(cfg.mixer_kind(i) != "attn" for i in range(cfg.n_layers)):
            raise ValueError(
                "paged serving runs the unified chunked-prefill path, which "
                "requires an all-attention model: SSM/conv state folds the "
                "whole sequence and cannot resume a chunked prompt"
            )
        # latent attention and held experts run on the paged path alone;
        # the sharded pool and the drafter have no latent or routed path,
        # so they are refused rather than run wrong
        if cfg.mla or cfg.experts_held or cfg.first_dense:
            what = f"{cfg.name} (latent attention, held experts or leading dense layers)"
            if not scfg.paged:
                raise ValueError(f"{what} is served by the paged engine only: paged=True")
            if scfg.shards is not None:
                raise ValueError(f"{what}: the sharded pool (shards) has no latent-cache path")
            if scfg.draft_k > 0:
                raise ValueError(f"{what}: speculative decoding (draft_k) is not supported")
        if scfg.draft_k > 0 and scfg.draft_config is not None and scfg.draft_config.mla:
            raise ValueError("a latent-attention drafter is not supported")
        # routing counters (moe.ROUTING_COUNTERS): trailing outputs of both
        # step programs where the model holds experts
        self._counters = cfg.experts_held > 0
        # paged -> unified: the mixed-dispatch loop is the only paged path
        self._unified = scfg.paged
        self._token_budget = (
            scfg.token_budget if scfg.token_budget is not None else scfg.max_prompt_len
        )
        if scfg.draft_k < 0:
            raise ValueError(f"draft_k={scfg.draft_k} must be >= 0")
        if scfg.draft_k > 0:
            if not scfg.paged:
                raise ValueError(
                    "draft_k (speculative decoding) requires paged=True: the "
                    "verify dispatch reads and writes K/V through the shared "
                    "block pool"
                )
            if self._token_budget < scfg.draft_k + 1:
                raise ValueError(
                    f"token_budget={self._token_budget} cannot fit one verify "
                    f"descriptor of q_len={scfg.draft_k + 1} (draft_k + 1)"
                )
            if scfg.draft_config is not None and scfg.draft_params is None:
                raise ValueError(
                    "draft_config without draft_params: a drafter with its "
                    "own architecture needs its own weights"
                )
            dcfg = scfg.draft_config if scfg.draft_config is not None else cfg
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"drafter vocab_size={dcfg.vocab_size} != target "
                    f"vocab_size={cfg.vocab_size}: greedy accept-prefix "
                    "compares token ids across the two models"
                )
            if any(dcfg.mixer_kind(i) != "attn" for i in range(dcfg.n_layers)):
                raise ValueError(
                    "draft_config must be all-attention: the drafter decodes "
                    "through its own paged pool"
                )
            self._draft_cfg = dcfg
            dparams = scfg.draft_params if scfg.draft_params is not None else params
            if self._mesh is not None:
                dparams = jax.device_put(
                    dparams, NamedSharding(self._mesh, PartitionSpec())
                )
            self._draft_params = dparams
        t_cap = scfg.max_new_tokens
        # dispatch observability: fused admit prefills (bucketed admission
        # benchmark), fused decode chunks, and unified mixed steps — the
        # O(1)-dispatch-per-step regression gauges
        self.admit_dispatches = 0
        self.admit_rows_total = 0
        self.decode_dispatches = 0
        self.mixed_dispatches = 0
        # prefix-cache observability (engine lifetime; serve passes report
        # window deltas AND these totals into the scheduler each pass)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefill_tokens_total = 0
        self.prefill_tokens_saved = 0
        self.prefix_shared_total = 0  # blocks adopted by reference (cumulative)
        # speculative-decoding observability (engine lifetime): one
        # drafter dispatch + one verify dispatch per spec round is the
        # O(2)-dispatch bound CI guards; accept rate and tokens/step
        # derive from the proposed/accepted/emitted tallies
        self.draft_dispatches = 0
        self.draft_fill_dispatches = 0  # drafter prefill-only (admission cost)
        self.spec_rounds = 0
        self.spec_tokens_proposed = 0
        self.spec_tokens_accepted = 0
        self.spec_tokens_emitted = 0
        # resident paged state: created lazily on first paged serve and
        # reused by every later call (reset_cache() drops it)
        self._pool: BlockPool | None = None
        self._row_tables: list[BlockTable] | None = None
        self._tables_h: np.ndarray | None = None
        self._cache = None
        self._index: PrefixIndex | None = None
        self._spill_store: HostBlockStore | None = None
        # drafter resident state (draft_k > 0): a second, independent
        # BlockPool + per-slot tables + paged cache for the drafter —
        # same block geometry as the target pool, sized by the drafter's
        # (smaller) layer stack.  No prefix index: the drafter re-prefills
        # every prompt in full through its own chunked fill lanes
        self._draft_pool: BlockPool | None = None
        self._draft_row_tables: list[BlockTable] | None = None
        self._draft_tables_h: np.ndarray | None = None
        self._draft_cache = None
        self._serving = False

        def prefill_fn(params, tokens, lengths, cache_len=cache_len):
            logits, cache = LM.prefill(cfg, pol, params, {"tokens": tokens}, cache_len=cache_len)
            # logits at each row's true last prompt position -> first token
            last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)[:, 0, :]
            return jnp.argmax(last, -1).astype(jnp.int32), cache

        def decode_loop(params, cache, first_tok, lengths):
            """Device-resident greedy decode: runs until every row has
            emitted EOS or max_new_tokens, with no host round-trips.
            Rows that are already done emit PAD (never fresh argmax)."""
            b = first_tok.shape[0]
            t_max = scfg.max_new_tokens
            out = jnp.zeros((b, t_max), jnp.int32).at[:, 0].set(first_tok)
            state = (jnp.int32(1), cache, first_tok, first_tok == EOS, out)

            def cond(st):
                t, _, _, done, _ = st
                return (t < t_max) & ~jnp.all(done)

            def body(st):
                t, cache, cur, done, out = st
                logits, cache = LM.decode_step(
                    cfg, pol, params, cache, cur[:, None], lengths + t - 1
                )
                nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
                nxt = jnp.where(done, PAD, nxt)  # finished rows stay PAD
                out = out.at[:, t].set(nxt)
                return (t + 1, cache, nxt, done | (nxt == EOS), out)

            t, _, _, _, out = jax.lax.while_loop(cond, body, state)
            return out, t

        def admit_rows(params, cache, cur, lengths, emitted, done, budget, out,
                       rows_tokens, slot_ids, row_lens, b_new):
            """Prefill ``g`` requests and scatter them into contiguous
            cache stripes ``slot_ids`` in a single fused call.  The
            bucketed admission path dispatches waiting requests in
            power-of-2 groups, so the jit trace count is bounded at
            log2(max_batch) group shapes and ``k`` queued requests cost
            O(log k) dispatches, not k."""
            first, row_cache = prefill_fn(params, rows_tokens, row_lens)
            cache = jax.tree.map(
                lambda c, rc: c.at[:, slot_ids].set(rc), cache, row_cache
            )
            g = rows_tokens.shape[0]
            cur = cur.at[slot_ids].set(first)
            lengths = lengths.at[slot_ids].set(row_lens)
            emitted = emitted.at[slot_ids].set(1)
            budget = budget.at[slot_ids].set(b_new)
            out = out.at[slot_ids].set(
                jnp.zeros((g, t_cap + 1), jnp.int32).at[:, 0].set(first)
            )
            done = done.at[slot_ids].set((first == EOS) | (b_new <= 1))
            return cache, cur, lengths, emitted, done, budget, out

        def cow_copy(cache, src, dst):
            return LM.paged_copy_block(cfg, cache, src, dst)

        def is_pool_leaf(leaf):
            # pool-indexed K/V leaves: (n_layer_blocks, n_pool + 1, bs, ...)
            # unsharded, (n_layer_blocks, shards, n_local + 1, bs, ...) sharded
            if not scfg.paged:
                return False
            if self._shards is not None:
                return (
                    leaf.ndim >= 4
                    and leaf.shape[1] == self._shards
                    and leaf.shape[2] == self._n_local + 1
                    and leaf.shape[3] == bs
                )
            return (
                leaf.ndim >= 3
                and leaf.shape[1] == self._n_pool_blocks + 1
                and leaf.shape[2] == bs
            )

        self._is_pool_leaf = is_pool_leaf

        def upload_block(cache, payload, b):
            """Re-admission upload: host-tier K/V payload (one array per
            pool leaf, in ``jax.tree.leaves`` order) lands in pool block
            ``b``.  One trace total — every block has the same shape.  On
            a sharded pool the GLOBAL id resolves to (shard, local), so
            the payload lands on the chunk's recorded owning shard."""
            leaves, treedef = jax.tree.flatten(cache)
            out, j = [], 0
            for leaf in leaves:
                if is_pool_leaf(leaf):
                    if self._shards is not None:
                        s, l = b // self._n_local, b % self._n_local
                        out.append(leaf.at[:, s, l].set(payload[j].astype(leaf.dtype)))
                    else:
                        out.append(leaf.at[:, b].set(payload[j].astype(leaf.dtype)))
                    j += 1
                else:
                    out.append(leaf)
            return jax.tree.unflatten(treedef, out)

        def mixed_rows(params, cache, cur, lengths, emitted, done, budget, out,
                       tok, q_start_h, q_len, is_decode, row_len, b_new, tables):
            """ONE unified engine step: every row — mid-prompt fill, fill
            completion, or 1-token decode — advances through a single
            ``LM.mixed_step`` dispatch driven by per-row ``(q_start,
            q_len)`` descriptors.  Decode rows (``is_decode``) read their
            token from ``cur`` at position ``lengths + emitted - 1`` —
            exactly the ``decode_chunk`` hot loop for one step, so the
            emitted/done/out updates below are bit-compatible with it.
            Fill rows write their prompt chunk's K/V into the pool and
            only touch slot state on the chunk that REACHES ``row_len``
            (``completes``): the final logits lane seeds the slot exactly
            like ``admit_rows``.  Rows with ``q_len == 0`` (budget-starved
            this step) are inert: their lanes score into the trash block
            and no state updates."""
            b = scfg.max_batch
            rows = jnp.arange(b)
            q_start = jnp.where(is_decode, lengths + emitted - 1, q_start_h)
            tok = tok.at[:, 0].set(jnp.where(is_decode, cur, tok[:, 0]))
            kw = {}
            if self._counters:
                # live lanes: within q_len, on a row that is not a done
                # decode; they share the token budget, the row width
                live = (jnp.arange(tok.shape[1])[None, :] < q_len[:, None]) & ~(
                    is_decode & done)[:, None]
                kw = dict(live=live, live_max=tok.shape[1], counters=True)
            logits, cache, *routed = LM.mixed_step(
                cfg, pol, params, tok, cache, tables, q_start, q_len, bs,
                mesh=self._mesh, **kw,
            )
            last = jnp.take_along_axis(
                logits, jnp.maximum(q_len - 1, 0)[:, None, None], axis=1
            )[:, 0, :]
            nxt = jnp.argmax(last, -1).astype(jnp.int32)
            completes = (~is_decode) & (q_len > 0) & (q_start + q_len >= row_len)
            emit_dec = is_decode & (q_len > 0) & ~done
            # decode lane: token lands at the row's own emitted offset
            idx = jnp.minimum(emitted, t_cap)
            out = out.at[rows, idx].set(jnp.where(emit_dec, nxt, out[rows, idx]))
            # fill completion: seed the slot like admit_rows does
            seeded = jnp.zeros((b, t_cap + 1), jnp.int32).at[:, 0].set(nxt)
            out = jnp.where(completes[:, None], seeded, out)
            cur = jnp.where(completes | emit_dec, nxt, cur)
            lengths = jnp.where(completes, row_len, lengths)
            budget = jnp.where(completes, b_new, budget)
            emitted = jnp.where(completes, 1, emitted + emit_dec)
            done = jnp.where(
                completes,
                (nxt == EOS) | (b_new <= 1),
                done | (emit_dec & ((nxt == EOS) | (emitted >= budget))),
            )
            return (cache, cur, lengths, emitted, done, budget, out, *routed)

        kd = scfg.draft_k

        def spec_mixed_rows(params, cache, cur, lengths, emitted, done, budget, out,
                            tok, q_start_h, q_len, is_spec, drafts, row_len, b_new,
                            tables):
            """ONE unified engine step in speculative mode: fill chunks
            advance exactly as in ``mixed_rows``, while each speculating
            row (``is_spec``) becomes a VERIFY descriptor ``(slot,
            q_start = lengths + emitted - 1, q_len <= draft_k + 1,
            kv_len)``: lane 0 carries the row's last committed token
            ``cur``, lanes 1..q_len-1 carry the drafter's proposals.  The
            target's per-lane argmaxes are what plain greedy decode would
            emit one token at a time, so ``accept_prefix`` commits the
            longest matching run plus one corrected token — bit-identical
            outputs, > 1 token per dispatch.

            Rollback is positional, not a device copy: only ``emitted``
            advances (by ``n_emit``), so rejected lanes' K/V sit BEYOND
            the committed position.  The next round's verify window
            starts at the new ``q_start`` and re-writes every stale
            position before any lane attends to it (the kernel's
            write-then-attend contract), so a rejection can never leak
            state; q_len-masked dead lanes scatter to the trash block as
            always."""
            b = scfg.max_batch
            rows = jnp.arange(b)
            q_start = jnp.where(is_spec, lengths + emitted - 1, q_start_h)
            tok = tok.at[:, 0].set(jnp.where(is_spec, cur, tok[:, 0]))
            tok = tok.at[:, 1 : kd + 1].set(
                jnp.where(is_spec[:, None], drafts, tok[:, 1 : kd + 1])
            )
            logits, cache = LM.verify_step(
                cfg, pol, params, tok, cache, tables, q_start, q_len, bs,
                mesh=self._mesh,
            )
            # fill rows: next token off the chunk's last live lane
            last = jnp.take_along_axis(
                logits, jnp.maximum(q_len - 1, 0)[:, None, None], axis=1
            )[:, 0, :]
            nxt = jnp.argmax(last, -1).astype(jnp.int32)
            completes = (~is_spec) & (q_len > 0) & (q_start + q_len >= row_len)
            # spec rows: per-lane targets + greedy accept-prefix
            tgt = jnp.argmax(logits[:, : kd + 1, :], -1).astype(jnp.int32)
            n_emit, can = accept_prefix(
                drafts, tgt, q_len=q_len, rem=budget - emitted, done=done
            )
            n_emit = jnp.where(is_spec, n_emit, 0)
            can = can & is_spec[:, None]
            # committed run lands at the row's own emitted offsets (the
            # decode_chunk ragged-merge pattern); clamped lanes rewrite
            # the spare t_cap column with its own value
            j = jnp.arange(kd + 1)
            idx = jnp.minimum(emitted[:, None] + j[None, :], t_cap)
            keep = out[rows[:, None], idx]
            out = out.at[rows[:, None], idx].set(jnp.where(can, tgt, keep))
            # fill completion seeds the slot exactly like admit_rows
            seeded = jnp.zeros((b, t_cap + 1), jnp.int32).at[:, 0].set(nxt)
            out = jnp.where(completes[:, None], seeded, out)
            last_emit = jnp.take_along_axis(
                tgt, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
            )[:, 0]
            cur = jnp.where(n_emit > 0, last_emit, cur)
            cur = jnp.where(completes, nxt, cur)
            lengths = jnp.where(completes, row_len, lengths)
            budget = jnp.where(completes, b_new, budget)
            emitted = jnp.where(completes, 1, emitted + n_emit)
            done = jnp.where(
                completes,
                (nxt == EOS) | (b_new <= 1),
                done | ((n_emit > 0) & ((last_emit == EOS) | (emitted >= budget))),
            )
            return cache, cur, lengths, emitted, done, budget, out

        def make_draft_rows(with_fill: bool):
            dcfg = getattr(self, "_draft_cfg", cfg)

            def draft_body(dparams, dcache, cur, dec_pos, d_dec_tables):
                # k greedy drafter steps — ONE host dispatch; each step
                # writes the fed token's K/V then attends, so a stale
                # (rejected) position is always re-written before read.
                # The loop rides mixed_step (q_len=1 lanes), the SAME
                # kernel path the target verifies through: under
                # self-speculation the proposal at a position is then the
                # identical computation to the target's verify lane, so
                # near-tied argmaxes cannot flip between the two models
                # (accept rate hits the drafter-quality ceiling instead
                # of fp-noise)
                one = jnp.ones((scfg.max_batch,), jnp.int32)

                def body(t, st):
                    tok, dc, c = st
                    logits, dc = LM.mixed_step(
                        dcfg, pol, dparams, tok[:, None], dc, d_dec_tables,
                        dec_pos + t, one, bs, mesh=self._mesh,
                    )
                    nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
                    return nxt, dc, c.at[:, t].set(nxt)

                c = jnp.zeros((scfg.max_batch, max(kd, 1)), jnp.int32)
                last, dcache, c = jax.lax.fori_loop(0, kd, body, (cur, dcache, c))
                # write the k-th proposal's K/V too (logits discarded): a
                # full accept advances the committed position PAST it, and
                # an unwritten hole there would corrupt every later draft
                # for the row — write-then-attend must cover all k
                # proposed positions, not just the k-1 the loop feeds
                _, dcache = LM.mixed_step(
                    dcfg, pol, dparams, last[:, None], dcache, d_dec_tables,
                    dec_pos + kd, one, bs, mesh=self._mesh,
                )
                return c, dcache

            if not with_fill:
                return draft_body

            def draft_rows(dparams, dcache, d_tok, d_q_start, d_q_len,
                           cur, dec_pos, d_tables, d_dec_tables):
                """Drafter fill chunks + k draft steps fused into ONE
                dispatch: rows still streaming their prompt into the
                drafter pool advance through a mixed step (q_len == 0
                rows are inert), then every drafter-ready row proposes
                ``draft_k`` greedy tokens.  Rows excluded from drafting
                this round arrive with an all-trash ``d_dec_tables``
                row, so their draft-loop writes land in the trash
                block."""
                _, dcache = LM.mixed_step(
                    dcfg, pol, dparams, d_tok, dcache, d_tables,
                    d_q_start, d_q_len, bs, mesh=self._mesh,
                )
                return draft_body(dparams, dcache, cur, dec_pos, d_dec_tables)

            return draft_rows

        def make_decode_chunk(paged: bool):
            def decode_chunk(params, cache, cur, lengths, emitted, done, budget, out,
                             n_steps, tables=None):
                """Fused decode of up to ``n_steps`` tokens across all
                slots.  Per-slot write offsets (``emitted``) make
                retire/admit cheap: a slot's output row is always its own
                [0, emitted) prefix.  The inner loop writes a dense
                (B, chunk) buffer by step index — exactly the lock-step
                hot loop — and the ragged merge into the per-slot offsets
                happens ONCE per chunk, so continuous batching adds no
                per-token bookkeeping to the decode path.  In paged mode
                every K/V read/write goes through ``tables``; the host
                guarantees each live row's table covers the chunk before
                dispatch (rows it could not grow arrive force-done)."""
                b = scfg.max_batch
                rows = jnp.arange(b)
                chunk = jnp.zeros((b, scfg.sched_chunk), jnp.int32)
                emitted0 = emitted

                def cond(st):
                    t = st[0]
                    return (t < n_steps) & ~jnp.all(st[4])

                def body(st):
                    t, cache, cur, emitted, done, chunk, routed = st
                    if paged:
                        kw = dict(live=~done, counters=True) if self._counters else {}
                        logits, cache, *step = LM.decode_step(
                            cfg, pol, params, cache, cur[:, None],
                            lengths + emitted - 1, block_tables=tables, block_size=bs,
                            mesh=self._mesh, **kw,
                        )
                        routed = [r + x for r, x in zip(routed, step)]
                    else:
                        logits, cache = LM.decode_step(
                            cfg, pol, params, cache, cur[:, None], lengths + emitted - 1
                        )
                    nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
                    nxt = jnp.where(done, PAD, nxt)
                    chunk = chunk.at[:, t].set(nxt)
                    emitted = emitted + (~done)
                    done = done | (nxt == EOS) | (emitted >= budget)
                    return (t + 1, cache, nxt, emitted, done, chunk, routed)

                # routing counters summed over the chunk's steps (none
                # for a model without held experts)
                routed = [jnp.zeros((len(MOE.ROUTING_COUNTERS),), jnp.int32)] * self._counters
                st = (jnp.int32(0), cache, cur, emitted, done, chunk, routed)
                _, cache, cur, emitted, done, chunk, routed = jax.lax.while_loop(cond, body, st)
                # ragged merge: row i's fresh tokens are chunk[i, :emitted-emitted0]
                # landing at out[i, emitted0:emitted]; invalid lanes are clipped
                # into the spare (t_cap) column, which holds no answer tokens
                j = jnp.arange(scfg.sched_chunk)
                idx = jnp.minimum(emitted0[:, None] + j[None, :], t_cap)
                valid = j[None, :] < (emitted - emitted0)[:, None]
                keep = out[rows[:, None], idx]
                out = out.at[rows[:, None], idx].set(jnp.where(valid, chunk, keep))
                return (cache, cur, emitted, done, out, *routed)

            return decode_chunk

        self._prefill = jax.jit(prefill_fn)
        self._decode_loop = jax.jit(decode_loop)
        self._admit_rows = jax.jit(admit_rows)
        self._cow_copy = jax.jit(cow_copy)
        self._upload_block = jax.jit(upload_block)
        self._mixed_rows = jax.jit(mixed_rows)
        self._decode_chunk = jax.jit(make_decode_chunk(scfg.paged))
        if scfg.draft_k > 0:
            self._spec_mixed_rows = jax.jit(spec_mixed_rows)
            self._draft_rows = jax.jit(make_draft_rows(with_fill=True))
            self._draft_tokens = jax.jit(make_draft_rows(with_fill=False))
        self.queue: list[np.ndarray] = []

    def submit(self, prompt_tokens: np.ndarray):
        self.queue.append(prompt_tokens.ravel())

    def _pack(self, prompts: list[np.ndarray]) -> np.ndarray:
        """Left-aligned PAD-tail packing; each row's decode slot is its own
        length (per-row positions), so ragged rows stay correct."""
        width = self.scfg.max_prompt_len
        out = np.zeros((len(prompts), width), np.int32)
        for i, p in enumerate(prompts):
            p = p[-width:]
            out[i, : len(p)] = p
        return out

    def _init_serve_cache(self):
        """Device cache for the continuous path in the configured layout."""
        dtype = jnp.dtype(self.cfg.dtype)
        if self.scfg.paged:
            if self._shards is not None:
                # per-shard slice holds its n_local blocks + its own trash
                return LM.init_paged_cache(
                    self.cfg, self._n_local + 1, self.scfg.block_size,
                    self.scfg.max_batch, dtype=dtype, n_shards=self._shards,
                )
            return LM.init_paged_cache(
                self.cfg, self._n_pool_blocks + 1, self.scfg.block_size,
                self.scfg.max_batch, dtype=dtype,
            )
        return LM.init_cache(self.cfg, self.scfg.max_batch, self._cache_len, dtype=dtype)

    def _place_sharded(self, cache):
        """Lay a sharded paged cache out over the mesh: pool leaves split
        on the shard axis ``P(None, "data", ...)``, per-slot leaves
        replicated — each device then holds exactly its shard's blocks."""
        pool_s = NamedSharding(self._mesh, PartitionSpec(None, "data"))
        repl_s = NamedSharding(self._mesh, PartitionSpec())
        return jax.tree.map(
            lambda leaf: jax.device_put(
                leaf, pool_s if self._is_pool_leaf(leaf) else repl_s
            ),
            cache,
        )

    def cache_nbytes(self) -> int:
        """HBM held by the continuous-path decode cache (both layouts),
        computed from abstract shapes — the denominator of every
        paged-vs-contiguous capacity comparison."""
        shapes = jax.eval_shape(self._init_serve_cache)
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(shapes))

    # ------------------------------------------------------------------ #
    # resident paged state
    # ------------------------------------------------------------------ #
    def _fetch_block(self, b: int):
        """Demotion callback for the tiered prefix cache: pull pool block
        ``b``'s K/V to host (one array per pool leaf, ``jax.tree.leaves``
        order) and return ``(payload, nbytes)``."""
        if self._shards is not None:
            s, l = b // self._n_local, b % self._n_local
            payload = [
                np.asarray(leaf[:, s, l])
                for leaf in jax.tree.leaves(self._cache)
                if self._is_pool_leaf(leaf)
            ]
        else:
            payload = [
                np.asarray(leaf[:, b])
                for leaf in jax.tree.leaves(self._cache)
                if self._is_pool_leaf(leaf)
            ]
        return payload, int(sum(p.nbytes for p in payload))

    def _ensure_paged_state(self):
        """Create the resident pool / tables / cache / index on first
        paged use; later serve calls reuse them (warm prefix cache)."""
        if self._pool is not None:
            return
        scfg = self.scfg
        n_shards = self._shards if self._shards is not None else 1
        self._pool = BlockPool(self._n_pool_blocks, scfg.block_size, n_shards=n_shards)
        self._row_tables = [BlockTable(self._pool) for _ in range(scfg.max_batch)]
        # every unallocated (or free-slot) table entry points at the
        # trash block, so masked writes can never land in live blocks
        # (on a sharded pool the global trash id resolves to every
        # shard's local trash — its "shard" n_pool // n_local matches none)
        self._tables_h = np.full(
            (scfg.max_batch, self._blocks_per_slot), self._trash_block, np.int32
        )
        self._cache = self._init_serve_cache()
        if self._shards is not None:
            self._cache = self._place_sharded(self._cache)
        if scfg.draft_k > 0:
            self._draft_pool = BlockPool(
                self._n_pool_blocks, scfg.block_size, n_shards=n_shards
            )
            self._draft_row_tables = [
                BlockTable(self._draft_pool) for _ in range(scfg.max_batch)
            ]
            self._draft_tables_h = np.full(
                (scfg.max_batch, self._blocks_per_slot), self._trash_block, np.int32
            )
            if self._shards is not None:
                self._draft_cache = self._place_sharded(LM.init_paged_cache(
                    self._draft_cfg, self._n_local + 1, scfg.block_size,
                    scfg.max_batch, dtype=jnp.dtype(self._draft_cfg.dtype),
                    n_shards=self._shards,
                ))
            else:
                self._draft_cache = LM.init_paged_cache(
                    self._draft_cfg, self._n_pool_blocks + 1, scfg.block_size,
                    scfg.max_batch, dtype=jnp.dtype(self._draft_cfg.dtype),
                )
        if scfg.prefix_cache:
            store = (
                HostBlockStore(scfg.spill_bytes)
                if scfg.spill_bytes is not None
                else None
            )
            self._spill_store = store
            self._index = PrefixIndex(
                self._pool, spill_store=store, fetch_block=self._fetch_block
            )

    def reset_cache(self):
        """Drop ALL resident paged state — device cache, block pool, prefix
        index, host spill tier.  The next serve call starts cold (used by
        benchmarks to compare cold vs warm arms on one engine)."""
        if self._serving:
            raise RuntimeError("reset_cache() during an active serve loop")
        self._pool = None
        self._row_tables = None
        self._tables_h = None
        self._cache = None
        self._index = None
        self._spill_store = None
        self._draft_pool = None
        self._draft_row_tables = None
        self._draft_tables_h = None
        self._draft_cache = None

    # ------------------------------------------------------------------ #
    # lock-step path (deterministic baseline)
    # ------------------------------------------------------------------ #
    def step_batch(self) -> list[np.ndarray]:
        """Serve up to max_batch queued requests; returns answer token rows."""
        if not self.queue:
            return []
        batch, self.queue = self.queue[: self.scfg.max_batch], self.queue[self.scfg.max_batch :]
        lengths = np.array(
            [min(len(p), self.scfg.max_prompt_len) for p in batch], np.int32
        )
        tokens = self._pack(batch)
        first, cache = self._prefill(self.params, jnp.asarray(tokens), jnp.asarray(lengths))
        out, n_steps = self._decode_loop(self.params, cache, first, jnp.asarray(lengths))
        ans = np.asarray(out)[:, : int(n_steps)]
        return [row for row in ans]

    # ------------------------------------------------------------------ #
    # continuous-batching path (slot pool + scheduler)
    # ------------------------------------------------------------------ #
    def serve(self, scheduler: Scheduler) -> dict[int, np.ndarray]:
        """Drive the slot pool until the scheduler's queue drains and every
        slot has retired (one-shot batch semantics: does NOT wait for more
        submissions).  Returns {rid: answer tokens}; per-request timestamps
        land in ``scheduler.results`` for latency stats.  On a resident
        paged engine, repeated calls reuse the prefix cache — the
        scheduler's top-level stats window covers this call."""
        return dict(self.serve_stream(scheduler, drain=True))

    def serve_stream(self, scheduler: Scheduler, *, drain: bool = False):
        """Generator form of ``serve``: yields ``(rid, answer_tokens)`` the
        moment a slot retires instead of returning one dict at drain, so a
        caller can stream results out (and overlap downstream work) while
        other slots keep decoding.

        With ``drain=False`` (default) the stream is *live*: when the
        queue is momentarily empty but the scheduler is still open, the
        engine keeps decoding active slots and then blocks in
        ``scheduler.wait_for_work`` — a producer thread may keep
        submitting until it calls ``scheduler.close()``, at which point
        the stream drains the remaining work and ends.  ``drain=True``
        restores the one-shot ``serve`` behavior: exit as soon as the
        queue is empty and every slot has retired, closed or not."""
        if self._unified:
            yield from self._serve_unified(scheduler, drain)
            return
        yield from self._serve_contiguous(scheduler, drain)

    def _serve_contiguous(self, scheduler: Scheduler, drain: bool):
        """Continuous batching over contiguous cache stripes: the parity
        oracle for the unified paged path (same admission order, same
        decode semantics, pow-2 bucketed admit prefills)."""
        scfg = self.scfg
        B, t_cap, width = scfg.max_batch, scfg.max_new_tokens, scfg.max_prompt_len
        scheduler.begin_window()
        cache = self._init_serve_cache()
        cur = jnp.zeros((B,), jnp.int32)
        lengths = jnp.ones((B,), jnp.int32)
        emitted = jnp.ones((B,), jnp.int32)
        done = jnp.ones((B,), bool)  # free slots read as done
        budget = jnp.ones((B,), jnp.int32)
        out = jnp.zeros((B, t_cap + 1), jnp.int32)
        slots: list[Request | None] = [None] * B
        # host mirrors of emitted/done/budget keep the loop at ONE device
        # sync per chunk; a just-admitted row's done flag is only known
        # on-device (first token may be EOS), so mirror it as live — the
        # worst case is one no-op chunk dispatch before the readback
        em_h = np.ones((B,), np.int64)
        dn_h = np.ones((B,), bool)
        bu_h = np.ones((B,), np.int64)
        steps = 0  # engine scheduler steps (dispatch-rate denominator)
        a0, d0 = self.admit_dispatches, self.decode_dispatches
        m0 = self.mixed_dispatches

        while True:
            # ---- admit queued requests into free slots (bucketed) ----
            admits: list[tuple[int, np.ndarray, int, int]] = []
            for slot in range(B):
                if slots[slot] is not None:
                    continue
                req = scheduler.pop_ready()
                if req is None:
                    break
                p = req.tokens[-width:]
                length = len(p)
                # prefill always emits one token, so the effective budget
                # floor is 1; None means "engine cap" (0 does not)
                b_new = t_cap if req.max_new_tokens is None else req.max_new_tokens
                b_new = max(1, min(int(b_new), t_cap))
                admits.append((slot, p, length, b_new))
                scheduler.record_tenant_admit(req.tenant, prefill_tokens=length)
                slots[slot] = req
                em_h[slot], dn_h[slot] = 1, b_new <= 1
                bu_h[slot] = b_new
            while admits:
                # power-of-2 buckets: k waiting requests prefill in
                # O(log k) fused dispatches, each a jit trace shared by
                # every future group of that size
                g = 1 << (len(admits).bit_length() - 1)
                group, admits = admits[:g], admits[g:]
                rows = np.zeros((g, width), np.int32)
                for i, (_, p, length, _) in enumerate(group):
                    rows[i, :length] = p
                slot_ids = np.array([s for s, _, _, _ in group], np.int32)
                row_lens = np.array([ln for _, _, ln, _ in group], np.int32)
                b_news = np.array([bn for _, _, _, bn in group], np.int32)
                cache, cur, lengths, emitted, done, budget, out = self._admit_rows(
                    self.params, cache, cur, lengths, emitted, done, budget, out,
                    jnp.asarray(rows), jnp.asarray(slot_ids), jnp.asarray(row_lens),
                    jnp.asarray(b_news),
                )
                self.admit_dispatches += 1
                self.admit_rows_total += g
            active = [i for i in range(B) if slots[i] is not None]
            scheduler.record_occupancy(free_slots=B - len(active))
            scheduler.record_dispatch_stats(
                admit_dispatches=self.admit_dispatches - a0,
                decode_dispatches=self.decode_dispatches - d0,
                mixed_dispatches=self.mixed_dispatches - m0,
                steps=steps,
                lifetime=self._dispatch_lifetime(),
            )
            if not active:
                if drain or scheduler.closed:
                    if scheduler.has_pending:
                        continue  # submit raced the close/empty check
                    return  # queue drained and every slot retired
                # live stream: idle until the producer submits or closes
                scheduler.wait_for_work()
                continue

            remaining = [int(bu_h[i] - em_h[i]) for i in active if not dn_h[i]]
            if remaining:
                # per-request budgets and EOS are enforced on-device, so the
                # chunk length is purely a scheduling granularity: run up to
                # the largest live budget but at most sched_chunk steps, so
                # freed slots wait at most sched_chunk for the next admit
                n = max(1, min(max(remaining), scfg.sched_chunk))
                cache, cur, emitted, done, out = self._decode_chunk(
                    self.params, cache, cur, lengths, emitted, done, budget, out,
                    jnp.int32(n),
                )
                self.decode_dispatches += 1
                steps += 1
            # np.array (not asarray): device views are read-only and the
            # mirrors are written at the next admit
            em_h, dn_h = np.array(emitted), np.array(done)
            _stamp_first_tokens(slots, em_h)

            retired = [i for i in active if dn_h[i]]
            if retired:
                out_h = np.asarray(out)
                for i in retired:
                    req = slots[i]
                    ans = out_h[i, : int(em_h[i])].copy()
                    scheduler.finish(req, ans)
                    slots[i] = None  # retire: slot free for the next admit
                    yield req.rid, ans

    @staticmethod
    def _geometry(slots, lengths, emitted, done, **descriptors) -> dict:
        """A dispatch's live geometry from the host mirrors, as it is
        launched: per row the prompt length, the tokens emitted, the done
        flag and the request id (-1 for a free slot), and the dispatch's
        own descriptors (fresh arrays each step, so not copied)."""
        return dict(lengths=lengths.copy(), emitted=emitted.copy(), done=done.copy(),
                    rids=[-1 if r is None else r.rid for r in slots], **descriptors)

    @staticmethod
    def _note_routing(disp, routed) -> None:
        """Attach a dispatch's routing counters (its trailing output, read
        in the read-back after ``emitted`` and ``done``, whose program has
        then finished) to its ``engine.dispatch`` span, while recording."""
        if routed and disp.recording:
            disp.attrs.update(zip(MOE.ROUTING_COUNTERS, (int(v) for v in np.array(routed[0]))))

    def _dispatch_lifetime(self) -> dict:
        return {
            "admit_dispatches": self.admit_dispatches,
            "decode_dispatches": self.decode_dispatches,
            "mixed_dispatches": self.mixed_dispatches,
            "draft_dispatches": self.draft_dispatches,
            "draft_fill_dispatches": self.draft_fill_dispatches,
            "spec_rounds": self.spec_rounds,
            "spec_tokens_proposed": self.spec_tokens_proposed,
            "spec_tokens_accepted": self.spec_tokens_accepted,
            "spec_tokens_emitted": self.spec_tokens_emitted,
        }

    def _serve_unified(self, scheduler: Scheduler, drain: bool):
        """Unified chunked-prefill serve loop — THE paged serving path.

        One ``_mixed_rows`` dispatch per engine step: each admitted
        request becomes a host-side *fill* record whose prompt is
        streamed into the pool ``token_budget`` query lanes at a time,
        sharing the step with the 1-lane decode rows.  Decode lanes are
        assigned first (a long prompt arrival chunks across steps instead
        of stalling in-flight decodes), fills consume the remaining lanes
        FIFO.  When no fill is in flight the loop falls back to the fused
        multi-step ``_decode_chunk`` — still one dispatch per step.  The
        jit trace count is O(1): every mixed step has the same static
        ``(max_batch, token_budget)`` shape.

        The pool, device cache, block tables, and prefix index are
        RESIDENT engine state (``_ensure_paged_state``): this loop picks
        them up warm and leaves them warm — retired prompt chains stay
        parked (or demoted to the host tier) for the next call.  A
        re-admitted (spilled) chunk is materialized synchronously via
        ``_upload_block`` before the row's first dispatch, so it never
        enters ``pending_blocks``.

        Prefix-cache cross-request ordering is host-side: chunks an
        in-flight fill has registered but not yet materialized sit in
        ``pending_blocks``; a later admission matching them waits (its
        fill stays unscheduled, see ``resolve_fill_deps``) until the
        owner's fill passes their last token.  Deps always point at
        earlier-admitted rows, so the wait graph is acyclic; if it ever
        stalled anyway, every blocked fill is force-retired with an empty
        ``deadlocked``-flagged answer rather than wedging the loop.
        """
        if self._serving:
            raise RuntimeError(
                "engine is already inside a serve loop; a resident engine "
                "serves one stream at a time"
            )
        scfg = self.scfg
        B, t_cap, width = scfg.max_batch, scfg.max_new_tokens, scfg.max_prompt_len
        bs, W = scfg.block_size, self._token_budget
        scheduler.begin_window()
        self._ensure_paged_state()
        pool, index = self._pool, self._index
        row_tables, tables_h = self._row_tables, self._tables_h
        store = self._spill_store
        if index is not None:
            lk0, ht0 = self.prefix_lookups, self.prefix_hits
            pt0, ps0 = self.prefill_tokens_total, self.prefill_tokens_saved
            sh0 = self.prefix_shared_total
            dm0, rm0 = index.n_demotions, index.n_readmits
        cur = jnp.zeros((B,), jnp.int32)
        lengths = jnp.ones((B,), jnp.int32)
        emitted = jnp.ones((B,), jnp.int32)
        done = jnp.ones((B,), bool)  # free slots read as done
        budget = jnp.ones((B,), jnp.int32)
        out = jnp.zeros((B, t_cap + 1), jnp.int32)
        slots: list[Request | None] = [None] * B
        em_h = np.ones((B,), np.int64)
        dn_h = np.ones((B,), bool)
        bu_h = np.ones((B,), np.int64)
        ln_h = np.ones((B,), np.int64)
        oom_slots: set[int] = set()
        empty = np.zeros((0,), np.int32)
        steps = 0
        a0, d0 = self.admit_dispatches, self.decode_dispatches
        m0 = self.mixed_dispatches
        # fills[slot]: in-flight prompt stream (p/length/b_new/pos/cow/deps);
        # None once the prompt has fully dispatched.  pending_blocks maps a
        # cached-chunk block an in-flight fill will write -> (owner slot,
        # token position at which its content exists on device)
        fills: list[dict | None] = [None] * B
        pending_blocks: dict[int, tuple[int, int]] = {}
        planned: dict[int, object] = {}
        # speculative decoding (draft_k > 0): the drafter mirrors the
        # target's fill machinery against its own pool.  d_fills[slot] is
        # the drafter's prompt stream (ALWAYS the full prompt — the
        # drafter has no prefix cache); a decode row speculates only once
        # its drafter fill completes (it sits out decode meanwhile — pure
        # scheduling, outputs are unaffected).  d_broken marks rows whose
        # drafter ran out of pool blocks mid-flight: they keep verifying
        # (garbage drafts can only be accepted when they MATCH the
        # target, so correctness never depends on the drafter)
        spec = scfg.draft_k > 0
        kd = scfg.draft_k
        d_pool = self._draft_pool
        d_row_tables = self._draft_row_tables
        d_tables_h = self._draft_tables_h
        d_fills: list[dict | None] = [None] * B
        d_broken = np.zeros((B,), bool)
        dr0, sr0 = self.draft_dispatches, self.spec_rounds
        sp0, sa0 = self.spec_tokens_proposed, self.spec_tokens_accepted
        se0, df0 = self.spec_tokens_emitted, self.draft_fill_dispatches
        self._serving = True

        def admit_gate(req: Request) -> bool:
            # dual-pool gate: the drafter re-prefills the full prompt, so
            # admission also requires drafter blocks for prompt + first
            # draft position (checked FIRST — a target-side prefix plan
            # is only memoized for requests that clear both pools)
            if spec and not d_pool.can_alloc(
                blocks_for(min(len(req.tokens), width) + 1, bs)
            ):
                return False
            if index is not None:
                plan = index.plan(req.tokens[-width:])
                if plan is not None:
                    planned[req.rid] = plan
                return plan is not None
            n_tok = min(len(req.tokens), width) + 1
            return pool.can_alloc(blocks_for(n_tok, bs))

        def report_prefix():
            if index is None:
                return
            window = {
                "prefix_lookups": self.prefix_lookups - lk0,
                "prefix_hits": self.prefix_hits - ht0,
                "prefill_tokens": self.prefill_tokens_total - pt0,
                "prefill_tokens_saved": self.prefill_tokens_saved - ps0,
                "prefix_shared_blocks": self.prefix_shared_total - sh0,
                "prefix_cached_blocks": index.n_cached_blocks,
            }
            lifetime = {
                "prefix_lookups": self.prefix_lookups,
                "prefix_hits": self.prefix_hits,
                "prefill_tokens": self.prefill_tokens_total,
                "prefill_tokens_saved": self.prefill_tokens_saved,
                "prefix_shared_blocks": self.prefix_shared_total,
                "prefix_cached_blocks": index.n_cached_blocks,
            }
            if store is not None:
                window.update(
                    spill_demotions=index.n_demotions - dm0,
                    spill_readmits=index.n_readmits - rm0,
                    spilled_blocks=index.n_spilled,
                    spill_bytes_used=store.used_bytes,
                )
                lifetime.update(
                    spill_demotions=index.n_demotions,
                    spill_readmits=index.n_readmits,
                    spilled_blocks=index.n_spilled,
                    spill_bytes_used=store.used_bytes,
                )
            scheduler.record_prefix_stats(window, lifetime)

        try:
            while True:
                with tracing.span("engine.step", step=steps) as step:
                    # ---- admit queued requests into free slots ----
                    # each admit is pure host bookkeeping (pool commit + fill
                    # record); NO device dispatch happens here — prompt tokens
                    # enter the device through the shared mixed step below
                    # (re-admitted spilled chunks are the one exception: their
                    # host payload uploads synchronously right here)
                    t_admit, n_admit = time.monotonic_ns(), 0
                    for slot in range(B):
                        if slots[slot] is not None:
                            continue
                        req = scheduler.pop_ready(admit_if=admit_gate)
                        if req is None:
                            break
                        p = req.tokens[-width:]
                        length = len(p)
                        b_new = t_cap if req.max_new_tokens is None else req.max_new_tokens
                        b_new = max(1, min(int(b_new), t_cap))
                        start, cow, deps = 0, None, set()
                        if index is not None:
                            plan = planned.pop(req.rid, None) or index.plan(p)
                            if plan is None:
                                raise RuntimeError("prefix admit raced the block pool")
                            table_ids, cow_dst = index.commit(plan)
                            for payload, b in plan.uploads:
                                # host-tier re-admission: K/V comes back by
                                # upload, not re-prefill; materialized before
                                # any dispatch reads it, so never "pending"
                                if payload:
                                    self._cache = self._upload_block(
                                        self._cache, payload, jnp.int32(b)
                                    )
                            row_tables[slot].adopt(table_ids)
                            tables_h[slot, :] = self._trash_block
                            tables_h[slot, : len(table_ids)] = table_ids
                            self.prefix_lookups += 1
                            self.prefill_tokens_total += length
                            start = plan.start
                            if start:
                                self.prefix_hits += 1
                                self.prefill_tokens_saved += start
                                self.prefix_shared_total += len(plan.shared) + (cow_dst is not None)
                            if cow_dst is not None and plan.cow_src is not None:
                                # device boundary copy still pending; a host
                                # (spilled) boundary already uploaded above
                                cow = (plan.cow_src, cow_dst)
                            # wait on shared/COW-source chunks another in-flight
                            # fill has registered but not yet computed
                            deps = {
                                b for b in (set(plan.shared) | ({plan.cow_src} if cow else set()))
                                if b in pending_blocks
                            }
                            for c in range(len(plan.nodes), length // bs):
                                pending_blocks[table_ids[c]] = (slot, (c + 1) * bs)
                        else:
                            tb = row_tables[slot]
                            if not tb.extend_to(length + 1):
                                raise RuntimeError("paged admit raced the block pool")
                            tables_h[slot, :] = self._trash_block
                            tables_h[slot, : tb.n_blocks] = tb.ids
                        scheduler.record_tenant_admit(
                            req.tenant, prefill_tokens=length,
                            prefill_tokens_saved=start, hit=start > 0,
                        )
                        slots[slot] = req
                        fills[slot] = dict(
                            p=p, length=length, b_new=b_new, pos=start, cow=cow, deps=deps
                        )
                        if spec:
                            d_tb = d_row_tables[slot]
                            if not d_tb.extend_to(length + 1):
                                raise RuntimeError("draft admit raced the draft pool")
                            d_tables_h[slot, :] = self._trash_block
                            d_tables_h[slot, : d_tb.n_blocks] = d_tb.ids
                            d_fills[slot] = dict(p=p, length=length, pos=0)
                            d_broken[slot] = False
                        # inert on device until the fill's last chunk seeds the
                        # slot (mixed_rows `completes`); done=True keeps any
                        # decode lane from touching it meanwhile
                        em_h[slot], dn_h[slot] = 0, True
                        bu_h[slot], ln_h[slot] = b_new, length
                        n_admit += 1
                    if n_admit:
                        tracing.record(
                            "engine.admit", t_admit, time.monotonic_ns(), admitted=n_admit
                        )

                    active = [i for i in range(B) if slots[i] is not None]
                    scheduler.record_occupancy(
                        free_slots=B - len(active),
                        free_blocks=pool.free_blocks,
                        reclaimable_blocks=pool.reclaimable_blocks if index is not None else None,
                        # drafter-pool headroom: without it a d_broken (drafter
                        # OOM) degradation is invisible in the memory gauges
                        draft_free_blocks=d_pool.free_blocks if spec else None,
                    )
                    report_prefix()
                    scheduler.record_dispatch_stats(
                        admit_dispatches=self.admit_dispatches - a0,
                        decode_dispatches=self.decode_dispatches - d0,
                        mixed_dispatches=self.mixed_dispatches - m0,
                        steps=steps,
                        lifetime=self._dispatch_lifetime(),
                        draft_dispatches=self.draft_dispatches - dr0,
                        draft_fill_dispatches=self.draft_fill_dispatches - df0,
                        spec_rounds=self.spec_rounds - sr0,
                        spec_tokens_proposed=self.spec_tokens_proposed - sp0,
                        spec_tokens_accepted=self.spec_tokens_accepted - sa0,
                        spec_tokens_emitted=self.spec_tokens_emitted - se0,
                    )
                    if not active:
                        step.drop()  # nothing to dispatch: not an engine step
                        if drain or scheduler.closed:
                            if scheduler.has_pending:
                                continue
                            return
                        with tracing.span("engine.wait"):
                            scheduler.wait_for_work()
                        continue

                    fill_rows = [i for i in range(B) if fills[i] is not None]
                    dec_rows = [i for i in active if fills[i] is None and not dn_h[i]]
                    try:
                        runnable = resolve_fill_deps(
                            {i: frozenset(fills[i]["deps"]) for i in fill_rows},
                            pending_blocks.keys(),
                        )
                    except AdmissionDeadlock as exc:
                        # every in-flight fill waits on a chunk nobody will
                        # write: unreachable with commit-ordered deps, but
                        # wedging the loop would be worse than degrading —
                        # roll back their cached-chunk registrations (one
                        # leaf-first call), drop COW pins, and retire them
                        # empty + deadlocked
                        doomed = set(exc.stuck)
                        inv = [b for b, (s, _) in pending_blocks.items() if s in doomed]
                        if index is not None and inv:
                            index.invalidate(inv)
                        for b in inv:
                            del pending_blocks[b]
                        for i in sorted(doomed):
                            fl, req = fills[i], slots[i]
                            if fl["cow"] is not None:
                                pool.free([fl["cow"][0]])
                            row_tables[i].release()
                            tables_h[i, :] = self._trash_block
                            if spec:
                                if d_row_tables[i].ids:
                                    d_row_tables[i].release()
                                d_tables_h[i, :] = self._trash_block
                                d_fills[i] = None
                            scheduler.finish(req, empty, deadlocked=True)
                            slots[i], fills[i] = None, None
                            em_h[i], dn_h[i] = 1, True
                            with step.suspended("engine.yield"):
                                yield req.rid, empty
                        continue

                    if spec:
                        # ---- speculative round: O(2) dispatches ----
                        # (1) ONE drafter dispatch: drafter prompt chunks for
                        #     rows still streaming + k greedy proposals for
                        #     every drafter-ready decode row
                        # (2) ONE target dispatch: verify descriptors
                        #     (q_len <= k+1) for speculating rows + target
                        #     fill chunks in the remaining token-budget lanes
                        # A decode row whose drafter fill is still streaming
                        # sits out (inert lane) — scheduling only, greedy
                        # outputs are position-independent
                        spec_rows = [i for i in dec_rows if d_fills[i] is None]
                        d_fill_rows = [i for i in range(B) if d_fills[i] is not None]
                        draft_ok: list[int] = []
                        for i in spec_rows:
                            if d_broken[i]:
                                continue
                            if int(bu_h[i] - em_h[i]) < 2 or (
                                int(self._cache_len_padded - (ln_h[i] + em_h[i] - 1)) < 2
                            ):
                                continue  # a 1-token tail can't accept any draft
                            # +1: the k-loop writes K/V for every proposal
                            # including d_k at dec_pos + kd (see draft_body)
                            need = int(ln_h[i] + em_h[i] + kd)
                            if need > self._cache_len_padded:
                                continue  # cache tail: draft to trash this round
                            d_tb = d_row_tables[i]
                            if d_tb.n_tokens_capacity < need:
                                n0 = d_tb.n_blocks
                                if d_tb.extend_to(need):
                                    d_tables_h[i, n0 : d_tb.n_blocks] = d_tb.ids[n0:]
                                else:
                                    # drafter pool OOM: drop its chain; the row
                                    # keeps verifying garbage drafts (an accept
                                    # requires a target MATCH, so outputs never
                                    # depend on the drafter)
                                    d_broken[i] = True
                                    d_row_tables[i].release()
                                    d_tables_h[i, :] = self._trash_block
                                    continue
                            draft_ok.append(i)
                        # rows excluded from drafting write into the trash block
                        d_dec_tab = np.full_like(d_tables_h, self._trash_block)
                        for i in draft_ok:
                            d_dec_tab[i] = d_tables_h[i]
                        dec_pos_h = (ln_h + em_h - 1).astype(np.int32)
                        drafts = None
                        if d_fill_rows:
                            d_tok = np.zeros((B, W), np.int32)
                            d_qs = np.zeros((B,), np.int32)
                            d_ql = np.zeros((B,), np.int32)
                            d_lanes = W
                            for i in d_fill_rows:
                                if d_lanes <= 0:
                                    break
                                fl = d_fills[i]
                                take = min(fl["length"] - fl["pos"], d_lanes)
                                d_tok[i, :take] = fl["p"][fl["pos"] : fl["pos"] + take]
                                d_qs[i] = fl["pos"]
                                d_ql[i] = take
                                d_lanes -= take
                                fl["pos"] += take
                                if fl["pos"] >= fl["length"]:
                                    d_fills[i] = None
                            with tracing.span("engine.launch", kind="draft"):
                                drafts, self._draft_cache = self._draft_rows(
                                    self._draft_params, self._draft_cache,
                                    jnp.asarray(d_tok), jnp.asarray(d_qs), jnp.asarray(d_ql),
                                    cur, jnp.asarray(dec_pos_h), jnp.asarray(d_tables_h),
                                    jnp.asarray(d_dec_tab),
                                )
                            # a dispatch that only streams drafter prompt
                            # chunks is admission overhead (the drafter's
                            # prefill), not a per-round cost
                            if draft_ok:
                                self.draft_dispatches += 1
                            else:
                                self.draft_fill_dispatches += 1
                        elif draft_ok:
                            with tracing.span("engine.launch", kind="draft"):
                                drafts, self._draft_cache = self._draft_tokens(
                                    self._draft_params, self._draft_cache, cur,
                                    jnp.asarray(dec_pos_h), jnp.asarray(d_dec_tab),
                                )
                            self.draft_dispatches += 1
                        tok = np.zeros((B, W), np.int32)
                        q_start_h = np.zeros((B,), np.int32)
                        q_len_h = np.zeros((B,), np.int32)
                        is_spec_h = np.zeros((B,), bool)
                        row_len_h = np.zeros((B,), np.int32)
                        b_new_h = np.ones((B,), np.int32)
                        oom = np.zeros((B,), bool)
                        lanes = W
                        # verify lanes first (fills absorb the wait), drafted
                        # rows before un-drafted ones: a round that paid for a
                        # drafter k-loop always lands >= one q_len >= 2 verify
                        draft_set = set(draft_ok)
                        for i in draft_ok + [r for r in spec_rows if r not in draft_set]:
                            if lanes <= 0:
                                break
                            rem = int(bu_h[i] - em_h[i])
                            space = int(self._cache_len_padded - (ln_h[i] + em_h[i] - 1))
                            v = min(kd + 1, rem, space, lanes)
                            if v < 1:
                                continue
                            need_tok = min(
                                ln_h[i] + em_h[i] - 1 + v, self._cache_len_padded
                            )
                            tb = row_tables[i]
                            if tb.n_tokens_capacity < need_tok:
                                n0 = tb.n_blocks
                                if tb.extend_to(int(need_tok)):
                                    tables_h[i, n0 : tb.n_blocks] = tb.ids[n0:]
                                else:
                                    oom[i] = True
                                    dn_h[i] = True
                                    oom_slots.add(i)
                                    continue
                            is_spec_h[i] = True
                            q_len_h[i] = v
                            lanes -= v
                        for i in runnable:
                            if lanes <= 0:
                                break
                            fl = fills[i]
                            if fl["cow"] is not None:
                                src, dst = fl["cow"]
                                self._cache = self._cow_copy(
                                    self._cache, jnp.int32(src), jnp.int32(dst)
                                )
                                pool.free([src])
                                fl["cow"] = None
                            take = min(fl["length"] - fl["pos"], lanes)
                            tok[i, :take] = fl["p"][fl["pos"] : fl["pos"] + take]
                            q_start_h[i] = fl["pos"]
                            q_len_h[i] = take
                            row_len_h[i] = fl["length"]
                            b_new_h[i] = fl["b_new"]
                            lanes -= take
                            fl["pos"] += take
                            mine = [
                                b for b, (s, e) in pending_blocks.items()
                                if s == i and e <= fl["pos"]
                            ]
                            for b in mine:
                                del pending_blocks[b]
                            if fl["pos"] >= fl["length"]:
                                fills[i] = None
                        if oom.any():
                            done = jnp.logical_or(done, jnp.asarray(oom))
                        if is_spec_h.any() or q_len_h.any():
                            em_before = em_h.copy()
                            with tracing.span("engine.dispatch", kind="spec") as disp:
                                if disp.recording:
                                    disp.attrs.update(self._geometry(
                                        slots, ln_h, em_h, dn_h, q_start=q_start_h, q_len=q_len_h,
                                        is_spec=is_spec_h, row_len=row_len_h))
                                with tracing.span("engine.launch"):
                                    (self._cache, cur, lengths, emitted, done, budget, out) = (
                                        self._spec_mixed_rows(
                                            self.params, self._cache, cur, lengths, emitted,
                                            done, budget, out,
                                            jnp.asarray(tok), jnp.asarray(q_start_h),
                                            jnp.asarray(q_len_h), jnp.asarray(is_spec_h),
                                            drafts if drafts is not None
                                            else jnp.zeros((B, kd), jnp.int32),
                                            jnp.asarray(row_len_h), jnp.asarray(b_new_h),
                                            jnp.asarray(tables_h),
                                        )
                                    )
                                self.mixed_dispatches += 1
                                steps += 1
                                with tracing.span("engine.readback"):
                                    em_h, dn_h = np.array(emitted), np.array(done)
                            _stamp_first_tokens(slots, em_h, fills)
                            if is_spec_h.any():
                                committed = em_h[is_spec_h] - em_before[is_spec_h]
                                self.spec_tokens_emitted += int(committed.sum())
                                self.spec_tokens_proposed += int(
                                    (q_len_h[is_spec_h] - 1).sum()
                                )
                                self.spec_tokens_accepted += int(
                                    np.maximum(committed - 1, 0).sum()
                                )
                                if (q_len_h[is_spec_h] > 1).any():
                                    self.spec_rounds += 1
                    elif runnable:
                        # ---- ONE mixed dispatch: decode lanes + fill chunks ----
                        tok = np.zeros((B, W), np.int32)
                        q_start_h = np.zeros((B,), np.int32)
                        q_len_h = np.zeros((B,), np.int32)
                        is_dec = np.zeros((B,), bool)
                        row_len_h = np.zeros((B,), np.int32)
                        b_new_h = np.ones((B,), np.int32)
                        oom = np.zeros((B,), bool)
                        lanes = W
                        for i in dec_rows:  # decode first: fills absorb the wait
                            if lanes <= 0:
                                break
                            need_tok = min(
                                ln_h[i] + min(em_h[i] + 1, bu_h[i]) - 1,
                                self._cache_len_padded,
                            )
                            tb = row_tables[i]
                            if tb.n_tokens_capacity < need_tok:
                                n0 = tb.n_blocks
                                if tb.extend_to(int(need_tok)):
                                    tables_h[i, n0 : tb.n_blocks] = tb.ids[n0:]
                                else:
                                    oom[i] = True
                                    dn_h[i] = True
                                    oom_slots.add(i)
                                    continue
                            is_dec[i] = True
                            q_len_h[i] = 1
                            lanes -= 1
                        for i in runnable:
                            if lanes <= 0:
                                break
                            fl = fills[i]
                            if fl["cow"] is not None:
                                # boundary copy must precede this fill's writes;
                                # the copy consumes the source's cache VALUE, so
                                # commit's pin drops immediately after dispatch
                                src, dst = fl["cow"]
                                self._cache = self._cow_copy(
                                    self._cache, jnp.int32(src), jnp.int32(dst)
                                )
                                pool.free([src])
                                fl["cow"] = None
                            take = min(fl["length"] - fl["pos"], lanes)
                            tok[i, :take] = fl["p"][fl["pos"] : fl["pos"] + take]
                            q_start_h[i] = fl["pos"]
                            q_len_h[i] = take
                            row_len_h[i] = fl["length"]
                            b_new_h[i] = fl["b_new"]
                            lanes -= take
                            fl["pos"] += take
                            # chunks this dispatch materializes become matchable
                            mine = [
                                b for b, (s, e) in pending_blocks.items()
                                if s == i and e <= fl["pos"]
                            ]
                            for b in mine:
                                del pending_blocks[b]
                            if fl["pos"] >= fl["length"]:
                                fills[i] = None  # completes in this dispatch
                        if oom.any():
                            done = jnp.logical_or(done, jnp.asarray(oom))
                        with tracing.span("engine.dispatch", kind="mixed") as disp:
                            if disp.recording:
                                disp.attrs.update(self._geometry(
                                    slots, ln_h, em_h, dn_h, q_start=q_start_h, q_len=q_len_h,
                                    is_decode=is_dec, row_len=row_len_h))
                                # the attention kernel's KV walk: live blocks vs its grid
                                kv_end = np.where(is_dec, ln_h + em_h - 1, q_start_h) + q_len_h
                                disp.attrs.update(
                                    kv_blocks_live=int((-(-kv_end[q_len_h > 0] // bs)).sum()),
                                    kv_blocks_grid=B * tables_h.shape[1])
                            args = (jnp.asarray(tok), jnp.asarray(q_start_h), jnp.asarray(q_len_h),
                                    jnp.asarray(is_dec), jnp.asarray(row_len_h),
                                    jnp.asarray(b_new_h), jnp.asarray(tables_h))
                            with tracing.span("engine.launch"):
                                res = self._mixed_rows(
                                    self.params, self._cache, cur, lengths, emitted, done,
                                    budget, out, *args,
                                )
                                (self._cache, cur, lengths, emitted, done, budget, out) = res[:7]
                            self.mixed_dispatches += 1
                            steps += 1
                            with tracing.span("engine.readback"):
                                em_h, dn_h = np.array(emitted), np.array(done)
                                self._note_routing(disp, res[7:])
                        _stamp_first_tokens(slots, em_h, fills)
                    elif dec_rows:
                        # no fill in flight: fused multi-step decode, one dispatch
                        remaining = [int(bu_h[i] - em_h[i]) for i in dec_rows]
                        n = max(1, min(max(remaining), scfg.sched_chunk))
                        oom = np.zeros((B,), bool)
                        for i in dec_rows:
                            need_tok = min(
                                ln_h[i] + min(em_h[i] + n, bu_h[i]) - 1,
                                self._cache_len_padded,
                            )
                            tb = row_tables[i]
                            if tb.n_tokens_capacity >= need_tok:
                                continue
                            n0 = tb.n_blocks
                            if tb.extend_to(int(need_tok)):
                                tables_h[i, n0 : tb.n_blocks] = tb.ids[n0:]
                            else:
                                oom[i] = True
                                dn_h[i] = True
                                oom_slots.add(i)
                        if oom.any():
                            done = jnp.logical_or(done, jnp.asarray(oom))
                        with tracing.span("engine.dispatch", kind="decode") as disp:
                            if disp.recording:
                                disp.attrs.update(
                                    self._geometry(slots, ln_h, em_h, dn_h, n_steps=n)
                                )
                            args = (jnp.int32(n), jnp.asarray(tables_h))
                            with tracing.span("engine.launch"):
                                res = self._decode_chunk(
                                    self.params, self._cache, cur, lengths, emitted, done,
                                    budget, out, *args,
                                )
                                self._cache, cur, emitted, done, out = res[:5]
                            self.decode_dispatches += 1
                            steps += 1
                            with tracing.span("engine.readback"):
                                em_h, dn_h = np.array(emitted), np.array(done)
                                self._note_routing(disp, res[5:])
                            if disp.recording:
                                disp.attrs["emitted_after"] = em_h.copy()
                        _stamp_first_tokens(slots, em_h, fills)

                    retired = [
                        i for i in active
                        if dn_h[i] and fills[i] is None and slots[i] is not None
                    ]
                    answered = []
                    if retired:
                        with tracing.span("engine.retire", rows=len(retired)):
                            out_h = np.asarray(out)
                            for i in retired:
                                req = slots[i]
                                ans = out_h[i, : int(em_h[i])].copy()
                                scheduler.finish(req, ans, truncated=i in oom_slots)
                                oom_slots.discard(i)
                                slots[i] = None
                                row_tables[i].release()
                                tables_h[i, :] = self._trash_block
                                if spec:
                                    if d_row_tables[i].ids:
                                        d_row_tables[i].release()
                                    d_tables_h[i, :] = self._trash_block
                                    d_fills[i] = None
                                    d_broken[i] = False
                                answered.append((req.rid, ans))
                    for rid, ans in answered:
                        # the consumer runs while the device has nothing queued
                        with step.suspended("engine.yield"):
                            yield rid, ans
        finally:
            # the pool/index outlive this call, so an abandoned stream must
            # not leak owned blocks or half-materialized chunk registrations
            # into the next serve.  Normal exit has already released
            # everything and this is a no-op
            if index is not None and pending_blocks:
                index.invalidate(list(pending_blocks))
            pending_blocks.clear()
            for i in range(B):
                if fills[i] is not None and fills[i].get("cow") is not None:
                    pool.free([fills[i]["cow"][0]])
                fills[i] = None
                if slots[i] is not None and slots[i].status == "active":
                    scheduler.finish(slots[i], empty, deadlocked=True)
                slots[i] = None
                if row_tables[i].ids:
                    row_tables[i].release()
                tables_h[i, :] = self._trash_block
                if spec:
                    d_fills[i] = None
                    if d_row_tables[i].ids:
                        d_row_tables[i].release()
                    d_tables_h[i, :] = self._trash_block
            report_prefix()
            self._serving = False

    def serve_prompts(
        self,
        prompts: Sequence[np.ndarray],
        max_new_tokens: int | Sequence[int] | None = None,
        deadlines: Sequence[float | None] | None = None,
    ) -> list[np.ndarray]:
        """Convenience wrapper: schedule ``prompts`` and serve to completion,
        returning answers in prompt order (expired requests -> empty row)."""
        sched = Scheduler()
        rids = sched.submit_many(prompts, max_new_tokens, deadlines)
        res = self.serve(sched)
        empty = np.zeros((0,), np.int32)
        return [res.get(rid, empty) for rid in rids]


def engine_generator(engine: ServeEngine, mode: str = "continuous") -> Callable:
    """Adapt a ServeEngine to the orchestrator's generator contract:
    callable (1, S) -> (1, T) for single prompts, plus ``generate_batch``
    (list of prompts -> list of answer rows).  ``mode="continuous"``
    (default) routes batches through the slot scheduler so ragged
    generations retire early; ``mode="lockstep"`` keeps the fixed-chunk
    baseline for determinism comparisons."""
    assert mode in ("continuous", "lockstep")

    def generate(prompt_tokens: np.ndarray) -> np.ndarray:
        if engine.queue:
            raise RuntimeError("engine_generator requires exclusive use of the engine queue")
        if mode == "continuous":
            return generate_batch([np.asarray(prompt_tokens)])[0][None, :]
        engine.submit(np.asarray(prompt_tokens))
        return engine.step_batch()[0][None, :]

    def generate_batch(prompts: list[np.ndarray]) -> list[np.ndarray]:
        if engine.queue:
            raise RuntimeError("engine_generator requires exclusive use of the engine queue")
        if mode == "continuous":
            return engine.serve_prompts([np.asarray(p) for p in prompts])
        for p in prompts:
            engine.submit(np.asarray(p))
        outs: list[np.ndarray] = []
        while engine.queue:
            outs.extend(engine.step_batch())
        return outs

    generate.generate_batch = generate_batch
    generate.engine = engine
    generate.mode = mode
    # advertise the engine's prompt window so prompt builders truncate
    # grammar-aware at the right width instead of leaving it to the
    # engine's blind tail-slice
    generate.max_prompt_len = engine.scfg.max_prompt_len
    return generate
