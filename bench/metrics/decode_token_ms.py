"""Mean, over the window's served queries with two answer tokens or more,
of the time from the first answer token to the last (the program's
``req.decode`` span) per token after the first."""
from bench.lib import spans


def value(run, cell):
    recs = spans.log()
    if recs is None:
        return None
    d = [(r.t1 - r.t0) / 1e6 / (r.attrs["tokens"] - 1)
         for r in spans.served_requests(recs, run, "req.decode") if r.attrs["tokens"] >= 2]
    return sum(d) / len(d) if d else None
