"""Bridge: the driver thread's own CPU seconds in a served partition's lane
layout (the program's ``route_cpu`` tracker, beside ``route``).
``bridge.route_ms_per_batch`` less this is what the thread waited inside
NumPy's argsort and scatters: for the GIL between them, or the scheduler.
Event-weighted mean over the window's batches. Nothing to read in a program
without the tracker."""


def read(run):
    n = run.delta("phase.route_cpu.count")
    return run.delta("phase.route_cpu.sum") / n * 1e3 if n else None
