"""Mean device-idle time between consecutive engine step programs in the
trace: the host's turn-around between two dispatches of the engine."""
import numpy as np

from bench.lib.derive import STEP_PROGRAMS
from bench.lib.trace import covered, union


def value(run, cell):
    red = run.trace
    if red is None:
        return None
    steps = red.programs(STEP_PROGRAMS)
    if len(steps) < 2:
        return None
    busy = union(red.ops)
    gaps = [(s1 - e0) - covered(busy, [(e0, s1)]) if s1 > e0 else 0.0
            for (_, e0), (s1, _) in zip(steps[:-1], steps[1:])]
    return float(np.mean(gaps)) / 1e6
