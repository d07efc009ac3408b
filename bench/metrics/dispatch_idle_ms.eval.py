"""Device-idle time of the traced window inside the engine's dispatches
(the program's ``engine.dispatch`` spans on the trace's clock), summed:
the device waiting while the host launches a step program or reads its
result back.  A stall inside a dispatch shows here."""
from bench.lib.spans import dispatch_idle_ms


def value(run, cell):
    return dispatch_idle_ms(run)
