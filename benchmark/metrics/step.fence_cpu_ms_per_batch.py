"""Driver + step, host side: the driver thread's own CPU seconds inside
the fence (the program's ``egress_fence_cpu`` tracker, ``StepRuntime._fence``):
near ``step.fence_wait_ms_per_batch`` where the wait for the device spins,
near 0 where it sleeps. Event-weighted mean over the window's batches.
Nothing to read in a program without the tracker."""


def read(run):
    n = run.delta("phase.egress_fence_cpu.count")
    return run.delta("phase.egress_fence_cpu.sum") / n * 1e3 if n else None
