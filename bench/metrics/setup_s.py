"""Seconds from the start of `bench/run.py` to the opening of the window:
JAX start-up, the compile cache, the benchmark's data, the service, its
warm-up and, for a backlog, the submission of the whole backlog."""


def read(run):
    return run.setup_s
