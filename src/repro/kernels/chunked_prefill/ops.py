"""Jitted public wrapper for the unified chunked-prefill attention kernel."""
import functools

import jax

from repro.kernels.chunked_prefill.kernel import mixed_prefill_attention_pallas
from repro.kernels.chunked_prefill.ref import (  # noqa: F401  (partials re-export)
    mixed_prefill_attention_ref,
    mixed_prefill_partials,
)


@functools.partial(jax.jit, static_argnames=("use_pallas", "scale", "v_dim"))
def mixed_prefill_attention(q, k_pool, v_pool, block_tables, desc, use_pallas: bool = False,
                            scale: float | None = None, v_dim: int | None = None):
    """Ragged mixed prefill/decode attention through a block table over a
    shared KV pool.  ``use_pallas=True`` streams pool blocks via
    scalar-prefetch index maps (interpreted on the CPU); the
    default gathers in XLA.

    ``desc`` is ``(B, 4)`` int32 rows ``(slot, q_start, q_len, kv_len)``.
    Three descriptor shapes cover every serving mode, all through the
    same write-then-attend contract (fresh lane K/V scatters into the
    pool before any lane attends, dead lanes ``>= q_len`` scatter to the
    trash block):

      * prefill chunk — ``q_len > 1``, ``q_start`` mid-prompt: resumes a
        chunked prompt at any boundary;
      * decode — ``q_len == 1`` at the row's next position;
      * speculative VERIFY — ``q_len == k + 1`` starting at the row's
        committed position: lane 0 carries the last committed token,
        lanes 1..k the drafter's proposals, and lane ``j``'s output
        equals a plain decode after emitting lanes ``< j``, which is
        what makes greedy accept-prefix bit-identical to 1-token decode.

    ``v_pool`` None: the values are K's first ``v_dim`` lanes (a latent
    head); ``scale`` None is 1/sqrt(dh).
    """
    if use_pallas:
        return mixed_prefill_attention_pallas(q, k_pool, v_pool, block_tables, desc,
                                              scale=scale, v_dim=v_dim)
    return mixed_prefill_attention_ref(q, k_pool, v_pool, block_tables, desc, scale, v_dim)
