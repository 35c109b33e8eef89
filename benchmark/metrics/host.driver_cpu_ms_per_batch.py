"""Host runtime: all the CPU seconds the ``device-driver`` thread used
for one batch (the program's ``driver_cpu`` tracker: ``time.thread_time``
from one batch's phases record to the next's in
``AsyncDeviceDriver._collect_oldest``, so ``_next_action``, the histograms'
own recording and ``on_drained`` too). With
``ingress.client_cpu_us_per_event`` times a batch's events it says whether
the two threads overlap: their sum against the batch's interval.
Event-weighted mean over the window's batches. Nothing to read in a program
without the tracker."""


def read(run):
    n = run.delta("phase.driver_cpu.count")
    return run.delta("phase.driver_cpu.sum") / n * 1e3 if n else None
