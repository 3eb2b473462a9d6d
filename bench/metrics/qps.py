"""qps: requests answered inside the closed loop's window over the window's
seconds. The window runs from the end of one batch to the end of a later
one, so it holds whole batches only."""


def read(run):
    answered = sum(1 for r in run.in_window if r.result is not None)
    return answered / (run.window.t1 - run.window.t0)
