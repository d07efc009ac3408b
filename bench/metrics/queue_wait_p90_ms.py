"""The 90th percentile, over the window's queries the engine admitted,
of the wait from submission to admission into a slot (the program's
``Request.submitted_at`` and ``started_at``)."""
from bench.lib import stats


def value(run, cell):
    w = [(q.started - q.submitted) * 1e3 for q in run.in_window()
         if q.started is not None and q.submitted is not None]
    return stats.percentile(w, 90) if w else None
