"""Device time of the program's Pallas kernels per query: op events the
name table fvb/kernels.json matches, in milliseconds."""


def read(run):
    f = run.fold
    if f is None or not f.n_chips or f.kernel_s <= 0 or not run.queries:
        return None
    return f.kernel_s / len(run.queries) * 1e3
