"""Ops completed in the window over the window's length: every op of
every request submitted in it, over the time from the first submit to
the last collect."""


def read(run):
    return sum(r.n_ops for r in run.requests) / run.window_s
