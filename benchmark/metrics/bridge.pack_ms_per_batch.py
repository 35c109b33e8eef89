"""Bridge: the builder's ``emit()`` under ``siddhi:seal.pack`` (the program's
``pack`` phase tracker), client thread with the engine lock held:
event-weighted mean over the window's batches."""


def read(run):
    n = run.delta("phase.pack.count")
    return run.delta("phase.pack.sum") / n * 1e3 if n else None
