"""Client / ingress: what an event costs the CLIENT's thread in CPU (the
program's ``client_cpu`` tracker: ``time.thread_time`` of the sealing thread
from one seal to the next in ``StepRuntime._emit_batch``, so the ``send`` /
``send_columns`` call chain and the caller's own loop round it, and none of
what that thread waited for: the GIL, the engine lock, ``submit``, its own
pacing). Event-weighted mean over the window's batches, over the mean
batch's events. Beside it on the wall clock: ``ingress.send_us_per_event``.
Where the kernel keeps thread CPU time by ticks and a short sleep reads as
CPU (the v5e's host, PERF.md section 7), a closed-loop client's poll loop is
in it: the client's own CPU only where the client is the bound. Nothing to
read in a program without the tracker."""


def read(run):
    n = run.delta("phase.client_cpu.count")
    events, steps = run.delta("probe.events"), run.delta("probe.steps")
    if not n or not events or not steps:
        return None
    return run.delta("phase.client_cpu.sum") / n / (events / steps) * 1e6
