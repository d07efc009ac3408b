"""The chunked-prefill attention kernel's share of its roofline in the
latent (absorbed MLA) geometry: the same reader as
``prefill_attn_roofline``, on a configuration whose architecture module
counts the work of 16 query heads over one 576-lane latent head (values
its first 512 lanes, read once).  The least time for the live lanes'
attention over the kernel's own device time (the ``tpu_custom_call`` in
the engine's mixed_rows program)."""
from bench.lib.derive import kernel_roofline

PROGRAM = r"jit_mixed_rows\("


def value(run, cell):
    return kernel_roofline(run, cell, PROGRAM, "prefill")
