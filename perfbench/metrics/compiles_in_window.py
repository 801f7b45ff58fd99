"""Executables compiled or loaded inside the window, counted by a
jax.monitoring listener on the backend-compile event. A warm window
reads 0."""


def read(run):
    return run.compiles_in_window
