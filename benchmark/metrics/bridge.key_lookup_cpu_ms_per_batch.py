"""Bridge: the driver thread's own CPU seconds in a served keyed window's key
lookup (the program's ``key_lookup_cpu`` tracker, beside ``key_lookup``).
``bridge.key_lookup_ms_per_batch`` less this is what the thread waited
there: for the GIL between NumPy's calls, or the scheduler. Event-weighted
mean over the window's batches. Nothing to read in a program without the
tracker."""


def read(run):
    n = run.delta("phase.key_lookup_cpu.count")
    return run.delta("phase.key_lookup_cpu.sum") / n * 1e3 if n else None
