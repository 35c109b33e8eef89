"""Egress: the share of the window's batches (event-weighted, as every
tracker is) whose decode read the blocked NFA's WHOLE candidate table
because a lane emitted more rows than the packed row table holds (the
program's ``decode_full`` tracker, span ``siddhi:collect.decode.full``, a
part of ``egress_decode``). 0 where the packed table always sufficed; near
100 and the pack buys nothing. Nothing to read in a program without the
tracker."""


def read(run):
    full = run.delta("phase.decode_full.count")
    n = run.delta("phase.egress_decode.count")
    return full / n * 100.0 if full is not None and n else None
