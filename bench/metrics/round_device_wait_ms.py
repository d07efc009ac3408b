"""Mean, over the federated rounds that start in the window (the program's
``fed.collect`` spans), of the time inside the round that the providers
spend blocked on a device result (the ``provider.embed.wait`` and
``provider.topk.wait`` spans under it, their union): the part of a round
that is queueing behind the engine's steps on the device."""
from bench.lib import spans


def value(run, cell):
    recs = spans.log()
    if recs is None:
        return None
    waits = [spans.cover_ns([r for r in spans.under(recs, c) if r.name.endswith(".wait")], c)
             for c in spans.starting_in_window(recs, run, "fed.collect")]
    return sum(waits) / len(waits) / 1e6 if waits else None
