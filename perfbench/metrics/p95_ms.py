"""95th percentile latency of all queries of the window, in milliseconds
(Python's inclusive quantiles; with few queries it lies near the slowest)."""
import statistics


def read(run):
    lat = run.latencies
    if not lat:
        return None
    if len(lat) == 1:
        return lat[0] * 1e3
    return statistics.quantiles(lat, n=20, method="inclusive")[18] * 1e3
