"""Completion: the mean `task.complete` span of the real-path tasks whose
completion closed in the window, on the worker thread from the start of
`Executor._complete` (predictor and surrogate learning) to the stored
result's release of the dispatch lock."""
from bench.trace import spans


def read(run):
    done = [b - a for a, b, _, args in spans.named(run.tracer_events,
                                                    "task.complete")
            if not args.get("offloaded") and run.in_window(b)]
    if not done:
        return None
    return 1e3 * sum(done) / len(done)
