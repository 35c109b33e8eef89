"""Window: the share of the window's batches (event-weighted, as every
tracker is) whose step fired a boundary with rows (the program's
``hop_flush`` tracker, span ``siddhi:collect.decode.hop_flush``, a part of
``egress_decode``): ``batch / hop`` is what the traffic says.
``step.device_ms_per_batch`` is a mean over all batches; with this share it
turns into the cost of one boundary. Nothing to read in a program without
the tracker."""


def read(run):
    fired = run.delta("phase.hop_flush.count")
    n = run.delta("phase.egress_decode.count")
    return fired / n * 100.0 if fired is not None and n else None
