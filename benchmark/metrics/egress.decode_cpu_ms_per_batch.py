"""Egress: the driver thread's own CPU seconds in ``collect`` after the
fence (the program's ``egress_decode_cpu`` tracker).
``egress.decode_ms_per_batch`` less this is what the thread waited in the
decode: blocking copies out, the GIL. Event-weighted mean over the window's
batches. Nothing to read in a program without the tracker."""


def read(run):
    n = run.delta("phase.egress_decode_cpu.count")
    return run.delta("phase.egress_decode_cpu.sum") / n * 1e3 if n else None
