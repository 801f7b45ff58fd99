"""Host time per query: the window's latency total minus the device's busy
time in it, over the queries, in milliseconds. With one client in a closed
loop this is the time the device waited on the host: wire, server,
finalize, the client's layout and merge."""


def read(run):
    f = run.fold
    if f is None or not f.n_chips or not run.queries:
        return None
    return (sum(run.latencies) - f.busy_s) / len(run.queries) * 1e3
