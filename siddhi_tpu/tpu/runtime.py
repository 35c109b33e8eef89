"""Device stream runtime: micro-batching front end over a compiled query.

Plays the role of the reference's ``StreamJunction`` + ``QueryRuntime`` pair for
the device path: host rows accumulate in a staging buffer; when a micro-batch
fills (or ``flush()`` is called) one jitted step runs on device and decoded rows
go to the callback.
"""

from __future__ import annotations

import contextlib
import logging

import jax
import numpy as np

from ..compiler import parse as _parse
from ..core.columns import ColumnsOut
from .batch import BatchBuilder
from .query_compile import CompiledStreamQuery
from .step_runtime import StepRuntime

log = logging.getLogger("siddhi_tpu.device")


def drain_hop_boundaries(compiled, state, drain_builder, on_out):
    """Hopping defers boundary flushes past the per-step flush capacity (a
    long time gap can span more hops than one step covers): step EMPTY
    batches until the next boundary is in the future, handing each step's
    outputs to ``on_out``. Returns the advanced state and its ``last_ts``
    (an empty step leaves it where it was)."""
    from .query_compile import _TS_NEG
    while True:
        hop_next, last_ts = (
            int(v) for v in jax.device_get(
                (state["hop_next"], state["last_ts"])))
        if hop_next <= _TS_NEG or hop_next > last_ts:
            return state, last_ts
        state, out = compiled.step(state, drain_builder.emit())
        on_out(out)


class DeviceStreamRuntime(StepRuntime):
    """The single-stream query's runtime: a ``BatchBuilder`` in front of one
    ``CompiledStreamQuery``. Built from a compiled plan by the bridge
    (``compiled=``), or from app text when used by itself."""

    def __init__(self, app_or_text=None, batch_capacity: int = 4096,
                 group_capacity: int = 1024, query_index: int = 0,
                 window_capacity: int = 4096, compiled=None):
        if compiled is None:
            app = _parse(app_or_text) if isinstance(app_or_text, str) \
                else app_or_text
            queries = app.queries
            if not queries:
                raise ValueError("no queries in app")
            query = queries[query_index]
            sid = query.input_stream.stream_id
            if sid not in app.stream_definitions:
                raise KeyError(f"stream '{sid}' not defined")
            compiled = CompiledStreamQuery(
                query, app.stream_definitions[sid], batch_capacity,
                group_capacity, window_capacity)
        self.compiled = compiled
        self.definition = compiled.definition
        self.builder = BatchBuilder(compiled.schema, compiled.B)
        # the step's own gauges, state scalars read at drain points
        # (on_drained): the steps whose batch the compaction had to move
        # (over the probe's `steps`: the share of batches a filter cut into)
        self.step_gauges: dict = {"compact_moves": 0}
        self._hopping = compiled.window_kind == "hopping"
        if self._hopping:
            # hopping's drain steps run inside _decode, on the driver's
            # thread in async mode: they take their empty batches from a
            # builder of their own, the live one may hold the NEXT batch's
            # rows by then ...
            self._drain_builder = BatchBuilder(compiled.schema, compiled.B)
            # ... and read live state back, which is that batch's own only
            # while nothing is in flight behind it: a batch whose step may
            # leave a boundary deferred is dispatched serial
            # (_hop_pipelined), the others pipeline. The boundaries a step
            # resolves: the grouped flush's `flush_cap`, the ungrouped B
            self._hop_cap = compiled.flush_cap if compiled.grouped_flush \
                else compiled.B
            # the newest timestamp stepped, filtered events included; None
            # until a drain has read the device's own back (deploy, restore,
            # a serial batch not yet drained)
            self._hop_newest = None
            # the batches dispatched serial since deploy: how often the
            # fallback engages (host-side, exact at every read)
            self.step_gauges["hop_serial_batches"] = 0
        # a grouped hopping flush hands out its rows compacted a boundary,
        # and the row counts are what the decode reads first; its window's
        # gauges join the step's
        if compiled.grouped_flush:
            self.fence_key = "nrows"
            self.step_gauges.update(window_live_keys=0,
                                    window_fill_share=0.0)
        self.state = compiled.init_state()
        # segment clock high-water: arrival ts, or the externalTimeBatch
        # attribute column
        self._tk_pos = (
            self.definition.attribute_position(compiled.time_key)
            if compiled.time_key is not None else None)
        self._last_clk = None
        self._warned: dict = {}     # overflow counters already warned of

    def send(self, row: list, timestamp: int = 0) -> None:
        clk = timestamp if self._tk_pos is None else row[self._tk_pos]
        if clk is not None:
            self._last_clk = clk if self._last_clk is None \
                else max(self._last_clk, clk)
        self.builder.append(row, timestamp)
        self._maybe_flush()

    def send_columns(self, cols, ts) -> None:
        """Bulk columnar staging: the chunk slice-copies into the builder
        (``append_columns``) across as many micro-batches as it spans —
        flush causes and adaptive thresholds behave exactly as per-event
        ``send``."""
        ts = np.asarray(ts, dtype=np.int64)
        n = int(ts.shape[0])
        if n == 0:
            return
        clk_col = ts
        if self._tk_pos is not None:
            col = cols[self.compiled.time_key]
            clk_col = np.asarray(
                col.materialize() if hasattr(col, "materialize") else col)
        try:
            clk = clk_col.max()
        except TypeError:    # object column with None values
            vals = [v for v in clk_col if v is not None]
            clk = max(vals) if vals else None
        if clk is not None:
            self._last_clk = clk if self._last_clk is None \
                else max(self._last_clk, clk)
        start = 0
        while start < n:
            take = self.builder.append_columns(cols, ts, start)
            start += take
            self._maybe_flush()
            if take == 0 and len(self.builder):
                # defensive: a full builder _maybe_flush did not drain (no
                # controller, capacity race)
                self.flush()

    def dispatch(self, batch: dict):
        if not self._hopping:
            self.state, out = self.compiled.step(self.state, batch)
            return out
        newest = self._hop_pipelined(batch)
        self.state, out = self.compiled.step(self.state, batch)
        self._hop_newest = newest
        if newest is None:
            # the driver dispatches nothing behind this batch until it is
            # collected (``_serial``), and its decode drains (``hop_serial``)
            batch["_serial"] = out["hop_serial"] = True
            self.step_gauges["hop_serial_batches"] += 1
        return out

    def _hop_pipelined(self, batch: dict):
        """The newest timestamp after this batch, where its step cannot
        leave a boundary deferred; None where it may, and the batch is
        serial: its decode drains the deferred ones from the live state,
        which is its own only while nothing is in flight behind it.
        Decided on the host from the batch's own timestamps: the boundaries
        a step fires lie in (the newest stepped before, the newest after
        it], because a pipelined batch leaves none deferred and a drain
        none due, so the next one the device holds lies past the newest
        stepped before. Those are at most ceil(span / H) instants against
        the ``_hop_cap`` a step resolves. A batch with no newest before it
        (after deploy or restore, or behind a serial batch whose drain never
        ran) is serial too; its drain reads the device's newest back."""
        prev, n = self._hop_newest, batch["count"]
        if prev is None:
            return None
        newest = max(prev, int(batch["ts"][:n].max())) if n else prev
        if -(-(newest - prev) // self.compiled.hop_ms) > self._hop_cap:
            return None
        return newest

    def _decode(self, out):
        """Hopping drains deferred boundary flushes here with empty steps,
        for a serial batch only (``_hop_pipelined``), and their chunks follow
        the batch's in order. Both are timed apart, inside
        ``egress_decode``: the decode of a batch whose step fired a boundary
        (``hop_flush``) and, for every batch, the drain's test with, where
        the batch is serial, the drain itself (``hop_drain``; its span only
        round a drain that runs)."""
        if not self._hopping:
            return self.compiled.decode_outputs(out)
        # the fence has fetched this output: rows out = a boundary fired
        fired = np.asarray(out[self.fence_key]).any()
        with self.measure("hop_flush") if fired \
                else contextlib.nullcontext():
            chunks = [self.compiled.decode_outputs(out)]
        serial = bool(out.get("hop_serial"))
        with self.measure("hop_drain", spanned=serial):
            if serial:
                self.state, self._hop_newest = drain_hop_boundaries(
                    self.compiled, self.state, self._drain_builder,
                    lambda o: chunks.append(self.compiled.decode_outputs(o)))
        return ColumnsOut.concat(chunks)

    def finalize(self) -> None:
        """Force-close the open timeBatch bucket at shutdown: a sentinel
        event two windows past the last segment-clock value closes the
        terminal bucket the way the host's boundary timer does (streams that
        stop sending must not lose their last bucket). For externalTimeBatch
        the sentinel carries the far-future value in the time ATTRIBUTE (the
        kernel's clock). The sentinel lands in its own far-future segment
        and never emits. Sessions need no terminal flush on this path:
        currents pass through per arrival."""
        if self.compiled.window_kind != "timeBatch" or \
                self._last_clk is None:
            return
        self.flush()
        sentinel = self._last_clk + 2 * max(int(self.compiled.window_ms), 1)
        row = [None] * len(self.compiled.schema.names)
        if self._tk_pos is not None:
            row[self._tk_pos] = sentinel
        # a guarded builder excludes the sentinel from its host-fallback
        # shadow (it is bookkeeping, not an event)
        append = getattr(self.builder, "append_sentinel",
                         self.builder.append)
        append(row, sentinel)
        self.flush()

    def on_drained(self) -> None:
        """Surface bounded-state overflow instead of silently diverging from
        the host semantics, and refresh the step's gauges. The counters are
        device scalars: read at drain points, in one copy, so they never
        stall the pipeline."""
        read = jax.device_get({
            key: self.state[key] for key in (
                "window_drops", "ts_regressions", "group_collisions",
                "compact_moves", "window_live_keys", "window_held")
            if key in self.state})
        for key, what in (("window_drops", "alive events evicted "
                           "(raise @device(window='N'))"),
                          ("ts_regressions", "out-of-order "
                           "timestamps clamped"),
                          ("group_collisions", "group-by keys "
                           "collided in the dense table (raise "
                           "@device key capacity)")):
            c = int(read.get(key, 0))
            if c > self._warned.get(key, 0):
                log.warning("query '%s': %d %s", self.query_name, c, what)
                self._warned[key] = c
        self.step_gauges["compact_moves"] = int(read.get("compact_moves", 0))
        if "window_held" in read:
            self.step_gauges["window_live_keys"] = \
                int(read["window_live_keys"])
            self.step_gauges["window_fill_share"] = \
                int(read["window_held"]) / max(self.compiled.window_n, 1)

    @property
    def group_collision_count(self) -> int:
        """Events whose group landed in a bucket owned by a different key
        (dense-table overflow: >K groups or a hash collision). Non-zero means
        those events' group aggregates are unreliable — widen
        ``group_capacity`` or keep the query on the host path."""
        c = self.state.get("group_collisions")
        return int(jax.device_get(c)) if c is not None else 0

    def block_until_ready(self) -> None:
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            self.state)

    # -- checkpointing: state is a pytree + the string dictionary ------------
    def snapshot_state(self) -> dict:
        from .batch import device_state_snapshot
        return device_state_snapshot(self.state, self.compiled.schema)

    def restore_state(self, state) -> None:
        from .batch import device_state_restore
        self.state = device_state_restore(state, self.compiled.schema)
        if self._hopping:
            self._hop_newest = None     # the next batch drains: serial
