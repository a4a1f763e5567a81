"""Completion: the mean `task.handoff` span closing in the window, from
the moment the worker stored a result to the return of the client's
`Executor.result` call."""
from bench.trace import spans


def read(run):
    handoffs = [b - a for a, b, _, _ in spans.named(run.tracer_events,
                                                    "task.handoff")
                if run.in_window(b)]
    if not handoffs:
        return None
    return 1e3 * sum(handoffs) / len(handoffs)
