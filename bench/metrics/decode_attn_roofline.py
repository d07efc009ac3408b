"""The paged decode attention kernel's share of its roofline: the least
time the chip could take for the live decode tokens' attention over the
kernel's own device time (the Pallas kernel is the
``tpu_custom_call`` in the engine's decode_chunk program).  The pool transposes outside the kernel are
not in it."""
from bench.lib.derive import kernel_roofline

PROGRAM = r"jit_decode_chunk\("


def value(run, cell):
    return kernel_roofline(run, cell, PROGRAM, "decode")
