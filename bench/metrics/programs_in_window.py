"""Predictor learning and every other source of new shapes: XLA programs
compiled or loaded from the persistent cache inside the window, counted
by `jax.monitoring`.  A warm service reads 0."""


def read(run):
    return run.programs_in_window
