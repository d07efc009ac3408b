"""Percentiles over requests, where a request that never succeeded counts
as missing: it sorts above every served one."""
from __future__ import annotations

import math


def percentile(values: list[float], q: float, n_missing: int = 0, missing_value: float = math.inf) -> float:
    """The ``q``-th percentile (0-100, nearest rank) of ``values`` plus
    ``n_missing`` requests that read ``missing_value``.  Nearest rank keeps
    the reading a real request's latency, never a blend with a missing
    one."""
    xs = sorted(values) + [missing_value] * n_missing
    if not xs:
        raise ValueError("percentile of no requests")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]
