"""Seconds from the process's start to the open of the window: imports,
device start, data, the served path, the pool write, and one query of
each instance (compiling, or loading from the compile cache)."""


def read(run):
    return run.setup_s
