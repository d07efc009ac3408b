"""Jitted public wrapper for the SSD intra-chunk kernel."""
import functools

import jax

from repro.kernels.ssd_scan.kernel import ssd_chunk_pallas
from repro.kernels.ssd_scan.ref import ssd_chunk_ref


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def ssd_chunk(x, b, c, dt, a, use_pallas: bool = False):
    if use_pallas:
        return ssd_chunk_pallas(x, b, c, dt, a)
    return ssd_chunk_ref(x, b, c, dt, a)
