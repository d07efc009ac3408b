"""Jitted public wrappers for flash-decode (contiguous + paged)."""
import functools

import jax

from repro.kernels.decode_attention.kernel import (
    combine_partials,
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref,
    paged_decode_attention_ref,
)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def decode_attention(q, k_cache, v_cache, lengths, use_pallas: bool = False):
    if use_pallas:
        return decode_attention_pallas(q, k_cache, v_cache, lengths)
    return decode_attention_ref(q, k_cache, v_cache, lengths)


@functools.partial(jax.jit, static_argnames=("use_pallas", "scale", "v_dim"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, use_pallas: bool = False,
                           scale: float | None = None, v_dim: int | None = None):
    """Single-token attention through a block table over a shared KV pool.
    ``use_pallas=True`` streams pool blocks via scalar-prefetch index maps
    (interpreted on the CPU); the default gathers in XLA.  ``v_pool`` None:
    the values are K's first ``v_dim`` lanes (a latent head); ``scale``
    None is 1/sqrt(dh)."""
    if use_pallas:
        return paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, lengths,
                                             scale=scale, v_dim=v_dim)
    return paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths, scale, v_dim)
