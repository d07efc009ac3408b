"""The 90th percentile, over the window's served queries, of the time from
admission into a slot to the read-back that brought the first answer
token (the program's ``req.prefill`` span): the prompt's passage through
the mixed steps."""
from bench.lib import spans, stats


def value(run, cell):
    recs = spans.log()
    if recs is None:
        return None
    d = [(r.t1 - r.t0) / 1e6 for r in spans.served_requests(recs, run, "req.prefill")]
    return stats.percentile(d, 90) if d else None
