"""Ingest: the mean host-clock time of a `ServiceBroker.submit` call made
in the window (push, cost, offload gate), as the client sees it."""


def read(run):
    calls = [c.t_submitted - c.t_submit for c in run.client
             if run.in_window(c.t_submit)]
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
