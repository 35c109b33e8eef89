"""Egress: a hopping window's drain after every batch (the program's
``hop_drain`` tracker, span ``siddhi:collect.decode.hop_drain``; a part of
``egress.decode_ms_per_batch``, not beside it), driver thread: the read of
``hop_next`` / ``last_ts`` out of the live state, which is why the runtime
keeps one step in flight, and any empty steps for deferred boundaries.
Event-weighted mean over the window's batches. Nothing to read in a program
without the tracker."""


def read(run):
    n = run.delta("phase.hop_drain.count")
    return run.delta("phase.hop_drain.sum") / n * 1e3 if n else None
