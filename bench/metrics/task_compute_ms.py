"""Task compute: the mean `compute_t` the executor measured for the
real-path tasks that ended in the window."""


def read(run):
    done = run.completed(real_only=True)
    if not done:
        return None
    return 1e3 * sum(r.compute_t for r in done) / len(done)
