"""Egress: the driver thread's own CPU seconds inside ``rt.deliver`` with
the engine lock held (the program's ``sink_publish_cpu`` tracker).
``egress.publish_ms_per_batch`` less this is what the thread waited while
it held the lock (the GIL, the scheduler). Event-weighted mean over the
window's batches that had rows. Nothing to read in a program without the
tracker."""


def read(run):
    n = run.delta("phase.sink_publish_cpu.count")
    return run.delta("phase.sink_publish_cpu.sum") / n * 1e3 if n else None
