"""Share of the traced window in which no operation ran on the device."""
from bench.lib.derive import idle_share


def value(run, cell):
    return idle_share(run, pending_only=False)
