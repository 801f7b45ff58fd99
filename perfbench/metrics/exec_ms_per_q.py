"""Device time of the fused request executable per query: the summed
duration of its module events in the traced window, in milliseconds."""


def read(run):
    f = run.fold
    if f is None or not f.n_chips or f.exec_s <= 0 or not run.queries:
        return None
    return f.exec_s / len(run.queries) * 1e3
