"""Driver: seal -> dispatch, the wait in the driver's ring and for the
dispatch window (the program's ``ingress_queue`` phase span, event-weighted
mean over the window)."""


def read(run):
    n = run.delta("phase.ingress_queue.count")
    return run.delta("phase.ingress_queue.sum") / n * 1e3 if n else None
