"""Driver + step, host side: the wait inside ``rt.collect`` until the step's
outputs are ready on the device and the first of them is on the host (the
program's ``egress_fence`` phase tracker once ``egress_decode`` is carved
out of it, span ``siddhi:collect.fence``). Nothing to read in a program that does not split
``collect``. Event-weighted mean over the window's batches."""


def read(run):
    n = run.delta("phase.egress_fence.count")
    if not n or run.delta("phase.egress_decode.count") is None:
        return None
    return run.delta("phase.egress_fence.sum") / n * 1e3
