"""Egress: the rest of ``rt.collect`` after the fence (the program's
``egress_decode`` phase tracker, span ``siddhi:collect.decode``): the other
copies to the host and the row loop. Event-weighted mean over the window's
batches."""


def read(run):
    n = run.delta("phase.egress_decode.count")
    return run.delta("phase.egress_decode.sum") / n * 1e3 if n else None
