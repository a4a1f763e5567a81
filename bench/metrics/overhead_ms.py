"""The paper's section IV-A scheduling overhead per task: over every task
completed in the window, the sum of (turnaround - cpu time), each at
least 0 as `TaskRecord.overhead` takes it, divided by the count.  Cpu time
is the server's init plus the task's compute time."""


def overhead_s(c):
    return max((c.t_done - c.t_submit) - (c.init_t + c.compute_t), 0.0)


def read(run):
    done = run.client_completed()
    if not done:
        return None
    return 1e3 * sum(overhead_s(c) for c in done) / len(done)
