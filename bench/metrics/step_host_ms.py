"""Mean, over the engine steps that start in the window (the program's
``engine.step`` spans: one loop pass that dispatches), of the step's
time outside its step programs' launch and read-back (``engine.launch``
and ``engine.readback``, which hold the wait on the device): descriptor
build, admission, retirement, and the consumer's turn at each yield."""
from bench.lib import spans

DEVICE = ("engine.launch", "engine.readback")


def value(run, cell):
    recs = spans.log()
    if recs is None:
        return None
    host = [(s.t1 - s.t0) - spans.cover_ns([r for r in spans.under(recs, s) if r.name in DEVICE], s)
            for s in spans.starting_in_window(recs, run, "engine.step")]
    return sum(host) / len(host) / 1e6 if host else None
