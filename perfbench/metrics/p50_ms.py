"""Median latency of all queries of the window, send to finalized answer
(with the client's merge for a group-by), in milliseconds."""
import statistics


def read(run):
    if not run.queries:
        return None
    return statistics.median(run.latencies) * 1e3
