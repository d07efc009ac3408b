"""The 90th percentile, over every query due in the window, of the time from its
scheduled arrival to the moment its answer reached the client; a query
that was not served counts as slower than every one that was."""
from bench.lib.derive import query_percentile


def value(run, cell):
    return query_percentile(run, 90)
