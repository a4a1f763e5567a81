"""Dispatch: the share of the window in which some thread holds the
executor's dispatch lock (the union of its `lock.held` spans)."""
from bench.trace import spans


def read(run):
    held = spans.named(run.tracer_events, "lock.held")
    if not held:
        return None
    return 100.0 * spans.union_in(held, run.t_open, run.t_close) \
        / run.window_s
