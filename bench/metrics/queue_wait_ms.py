"""Queue: the mean duration of the program's `task.queued` Tracer spans
(queue entry to the dispatch decision) that closed in the window."""


def read(run):
    events = run.tracer_events or []
    waits = [dur for ts, ph, name, _, _, dur, _ in events
             if ph == "X" and name == "task.queued"
             and run.in_window(ts + dur)]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
