"""Share of the time with a query inside the engine (submitted, not yet
answered) in which no operation ran on the device; idling for lack of
arrivals does not count."""
from bench.lib.derive import idle_share


def value(run, cell):
    return idle_share(run, pending_only=True)
