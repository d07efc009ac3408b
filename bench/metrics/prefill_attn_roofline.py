"""The chunked-prefill attention kernel's share of its roofline: the least
time the chip could take for the live lanes' attention (FLOPs at the bf16
peak or bytes at HBM bandwidth, whichever is larger) over the kernel's
own device time (the Pallas kernel is the
``tpu_custom_call`` in the engine's mixed_rows program).  The pool transposes outside the kernel are not in it."""
from bench.lib.derive import kernel_roofline

PROGRAM = r"jit_mixed_rows\("


def value(run, cell):
    return kernel_roofline(run, cell, PROGRAM, "prefill")
