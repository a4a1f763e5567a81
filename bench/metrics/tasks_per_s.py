"""Tasks that ended `ok` in the window, real and offloaded, over the
window's seconds (host clock)."""


def read(run):
    done = [r for r in run.completed() if r.status == "ok"]
    return len(done) / run.window_s
