"""Egress: the driver thread asking for the engine lock until it is held (the
program's ``lock_wait`` phase tracker, span ``siddhi:deliver.lock``), over
the window's batches that had rows, event-weighted."""


def read(run):
    n = run.delta("phase.lock_wait.count")
    return run.delta("phase.lock_wait.sum") / n * 1e3 if n else None
