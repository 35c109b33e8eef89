"""Egress: the wait for the engine lock plus the publishing under it (the
program's ``lock_wait`` and ``sink_publish`` phase trackers, span
``siddhi:deliver``), over the window's batches that had rows,
event-weighted."""


def read(run):
    n = run.delta("phase.sink_publish.count")
    lock, publish = (run.delta("phase.lock_wait.sum"),
                     run.delta("phase.sink_publish.sum"))
    if not n or lock is None:
        return None
    return (lock + publish) / n * 1e3
