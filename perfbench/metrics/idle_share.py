"""Share of the traced window in which no operation ran on the device:
1 - (union of busy intervals) / window, in percent, mean over chips."""


def read(run):
    f = run.fold
    if f is None or not f.n_chips or f.window_s <= 0:
        return None
    return 100.0 * (1.0 - f.busy_s / f.window_s)
