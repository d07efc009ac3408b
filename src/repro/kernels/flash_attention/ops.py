"""Jitted public wrapper for flash attention."""
import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "q_offset"))
def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0):
    del q_offset  # full-sequence prefill only; decode uses decode_attention
    return flash_attention_pallas(q, k, v, causal=causal)
