"""Bridge: a served partition's lane layout of the flat batch inside
``dispatch`` (the program's ``route`` tracker, span
``siddhi:dispatch.route``; a part of ``step.dispatch_ms_per_batch``, not
beside it), driver thread: event-weighted mean over the window's batches.
Nothing to read in a program without the tracker."""


def read(run):
    n = run.delta("phase.route.count")
    return run.delta("phase.route.sum") / n * 1e3 if n else None
