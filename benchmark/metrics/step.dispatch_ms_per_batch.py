"""Driver + step, host side: the host's time inside ``rt.dispatch`` (the
program's ``device_step`` phase tracker, span ``siddhi:dispatch``): copies
in and the launch. An enqueue, not device time. Event-weighted mean over the
window's batches."""


def read(run):
    n = run.delta("phase.device_step.count")
    return run.delta("phase.device_step.sum") / n * 1e3 if n else None
