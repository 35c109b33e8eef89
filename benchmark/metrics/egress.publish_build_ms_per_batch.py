"""Egress: what the ENGINE builds inside ``rt.deliver`` for a subscriber
that takes events (the program's ``publish_build`` tracker, span
``siddhi:deliver.publish.build``; a part of ``egress.publish_ms_per_batch``,
not beside it): the rows and the ``Event`` list a ``StreamCallback``'s
receiver builds from a columnar chunk (``core/stream.py``
``receive_columns``), or, where the chunk goes out as events, the timestamps,
rows and a ``StreamEvent`` a row and the ``Event`` list of a query callback
(``core/egress.py``). 0 for a subscriber that takes the columns as they are;
the rest of ``publish`` is the junction and the subscriber's own function.
Event-weighted mean over the window's batches that had rows. Nothing to read
in a program without the tracker."""


def read(run):
    n = run.delta("phase.publish_build.count")
    return run.delta("phase.publish_build.sum") / n * 1e3 if n else None
