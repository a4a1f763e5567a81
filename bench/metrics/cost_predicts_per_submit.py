"""Ingest: over the `svc.submit` spans that begin in the window, the mean
number of runtime-predictor calls (`predictor.predict` spans) a submit
makes to cost its task."""
from bench.trace import spans


def read(run):
    events = run.tracer_events
    submits = [s for s in spans.named(events, "svc.submit")
               if run.in_window(s[0])]
    if not submits:
        return None
    predicts = spans.inside(submits, spans.named(events, "predictor.predict"))
    return sum(len(p) for p in predicts) / len(submits)
