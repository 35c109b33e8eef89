"""Egress: ``rt.deliver(rows)`` with the engine lock held (the program's
``sink_publish`` phase tracker once ``lock_wait`` is carved out of it, span
``siddhi:deliver.publish``). Nothing to read in a program that does not tell
the lock wait apart. Event-weighted mean over the window's batches that had
rows."""


def read(run):
    n = run.delta("phase.sink_publish.count")
    if not n or run.delta("phase.lock_wait.count") is None:
        return None
    return run.delta("phase.sink_publish.sum") / n * 1e3
