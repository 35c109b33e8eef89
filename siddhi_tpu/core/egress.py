"""The one egress shape of the batched tiers: a step's output chunk goes to
the output junction whole.

The columnar host bridge and the device bridge both end a step with a
:class:`~siddhi_tpu.core.columns.ColumnsOut`. What happens to it is decided
from what is in front of it, per chunk:

- no query callback, no rate limiter, and every subscriber of the output
  junction takes columns (``StreamJunction.columns_capable``: a
  ``RowsCallback``, a rows sink, a downstream bridge's columnar receiver, or
  nobody at all) → ``deliver_columns(decoded, ts, n)``; no ``Event`` or
  ``StreamEvent`` is ever built;
- otherwise rows are built once, in bulk (``ColumnsOut.rows``), wrapped one
  ``StreamEvent`` a row, and handed over as ONE chunk: through the rate
  limiter when the query has one, to the query callbacks, and through one
  ``send_events(chunk)`` — chunk-aware receivers (``receive_chunk``: a
  ``StreamCallback``, a host bridge, a chunk window) see the batch as a
  batch.

There is no per-row path. ``egress`` counts deliveries and rows by shape, so
rows per delivery can be read (``egress_report``). What the ENGINE builds for
a subscriber that takes events is timed apart from what the subscriber does
with it: on the second path the timestamps, the rows, a ``StreamEvent`` a
row and the ``Event`` list of a query callback; on the first the ``Event``
list a ``StreamCallback``'s receiver builds from the columns
(``core/stream.py`` ``receive_columns``, which times it apart from the
user's function). Both are filed in the runtime's parts as ``publish_build``
(``observability/phases.py``): 0 for a subscriber that takes the columns as
they are.
"""

from __future__ import annotations

import numpy as np

from .event import Event, EventType, StreamEvent


class ChunkEgress:
    """Mixin of the bridges (``output_junction``, ``query_callbacks``,
    ``runtime``, ``query_name``)."""

    rate_limiter = None

    def _init_egress(self) -> None:
        # shape -> [deliveries, rows]
        self.egress = {"columns": [0, 0], "events": [0, 0]}

    def _building(self):
        """Round what the engine builds for a subscriber that takes
        events."""
        return self.runtime.measure("publish_build")

    def _on_out(self, out) -> None:
        """``out`` is a stamped :class:`~siddhi_tpu.core.columns.ColumnsOut`
        (``ts`` per row)."""
        if out is None or not out.n:
            return
        oj = self.output_junction
        if self.rate_limiter is None and not self.query_callbacks:
            if oj is None:
                return
            if oj.columns_capable():
                self._deliver_columns_out(out, oj)
                return
        self._deliver_events_out(out)

    def _deliver_columns_out(self, out, oj) -> None:
        # zero-object egress: dictionary codes decode to value columns (one
        # vectorized take per string column), no per-row object of any kind
        cols = out.decoded()
        names = oj.definition.attribute_names
        if list(cols) != names:
            # insert-into matches attributes by position
            cols = dict(zip(names, cols.values()))
        count = self.egress["columns"]
        count[0] += 1
        count[1] += out.n
        oj.deliver_columns(cols, np.asarray(out.ts, dtype=np.int64), out.n)
        # a StreamCallback asked for events: its receiver built them from
        # the columns and says what that took
        self.runtime.file_part("publish_build", sum(
            getattr(r, "built_s", 0.0) for r in oj.receivers))

    def _deliver_events_out(self, out) -> None:
        cur = EventType.CURRENT
        with self._building():
            events = [StreamEvent(ts, row, cur)
                      for ts, row in zip(out.ts_list(), out.rows())]
        if self.rate_limiter is not None:
            self.rate_limiter.process(events)   # → _publish_events
        else:
            self._publish_events(events)

    def _publish_events(self, events: list) -> None:
        """One chunk of output events to the query callbacks and the output
        junction."""
        if not events:
            return
        count = self.egress["events"]
        count[0] += 1
        count[1] += len(events)
        if self.query_callbacks:
            with self._building():
                evs = [Event(e.timestamp, e.data) for e in events]
            for cb in self.query_callbacks:
                cb.receive(events[-1].timestamp, evs, None)
        if self.output_junction is not None:
            self.output_junction.send_events(events)

    def egress_report(self) -> dict:
        """{shape: {deliveries, rows, rows_per_delivery}}."""
        return {shape: {"deliveries": d, "rows": r,
                        "rows_per_delivery": round(r / d, 3) if d else 0.0}
                for shape, (d, r) in self.egress.items()}
