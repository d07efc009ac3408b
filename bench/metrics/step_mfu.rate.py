"""The engine step's share of the chip's bf16 peak: useful FLOPs of the
traced dispatches (live tokens only) over the device time of the
engine's programs."""
from bench.lib.derive import step_mfu


def value(run, cell):
    return step_mfu(run, cell)
