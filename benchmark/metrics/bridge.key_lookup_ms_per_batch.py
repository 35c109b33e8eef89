"""Bridge: a served keyed window's way from a key to its slot inside
``dispatch`` (the program's ``key_lookup`` tracker, span
``siddhi:dispatch.key_lookup``; a part of ``step.dispatch_ms_per_batch``,
not beside it): the directory's search and the keys it admits, driver
thread; event-weighted mean over the window's batches. Nothing to read in a
program without the tracker."""


def read(run):
    n = run.delta("phase.key_lookup.count")
    return run.delta("phase.key_lookup.sum") / n * 1e3 if n else None
