"""Jitted step: the share of the window's batches (event-weighted, as every
tracker is) whose step ran the scan NFA's WHOLE pack, the row table of the
plan's bound, because a lane emitted more rows than the packed table holds
(a row an event of the lane's batch). The step chooses on the device by the
lanes' row counts ``n``; the decode reads the same ``n`` against the same
size and then reads ``full``, so the program's ``decode_full`` tracker
(span ``siddhi:collect.decode.full``, a part of ``egress_decode``) counts
those batches. 0 where the packed table always sufficed; near 100 and the
step pays both the count and the whole pack on every batch. Nothing to read
in a program without the tracker."""


def read(run):
    full = run.delta("phase.decode_full.count")
    n = run.delta("phase.egress_decode.count")
    return full / n * 100.0 if full is not None and n else None
