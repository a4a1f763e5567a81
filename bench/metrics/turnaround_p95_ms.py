"""95th percentile, nearest rank, over every task completed in the window,
of the time from the client's `submit` call to its result (host clock)."""
import math


def p95(values):
    s = sorted(values)
    return s[math.ceil(0.95 * len(s)) - 1]


def read(run):
    done = run.client_completed()
    if not done:
        return None
    return 1e3 * p95([c.t_done - c.t_submit for c in done])
