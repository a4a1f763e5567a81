"""Ingest: over the `svc.submit` spans that begin in the window, the mean
time a submit spends waiting for the executor's dispatch lock (the
`lock.wait` spans inside it on its thread)."""
from bench.trace import spans


def read(run):
    events = run.tracer_events
    submits = [s for s in spans.named(events, "svc.submit")
               if run.in_window(s[0])]
    if not submits:
        return None
    waits = spans.inside(submits, spans.named(events, "lock.wait"))
    return 1e3 * sum(b - a for w in waits for a, b, _, _ in w) / len(submits)
