"""Driver + step, host side: the driver thread's own CPU seconds inside
``rt.dispatch`` (the program's ``device_step_cpu`` tracker:
``time.thread_time`` at the two places ``device_step`` reads the wall
clock). ``step.dispatch_ms_per_batch`` less this is what the thread waited
in there: for the GIL, a lock or the scheduler. Event-weighted mean over the
window's batches. Nothing to read in a program without the tracker."""


def read(run):
    n = run.delta("phase.device_step_cpu.count")
    return run.delta("phase.device_step_cpu.sum") / n * 1e3 if n else None
