"""Device stream runtime: micro-batching front end over a compiled query.

Plays the role of the reference's ``StreamJunction`` + ``QueryRuntime`` pair for
the device path: host rows accumulate in a staging buffer; when a micro-batch
fills (or ``flush()`` is called) one jitted step runs on device and decoded rows
go to the callback.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax

from ..compiler import parse as _parse
from ..query_api import Query, SiddhiApp
from .batch import BatchBuilder
from .query_compile import CompiledStreamQuery


def drain_hop_boundaries(compiled, state, drain_builder, on_out):
    """Hopping defers boundary flushes past the per-step flush capacity (a
    long time gap can span more hops than one step covers): step EMPTY
    batches until the next boundary is in the future, handing each step's
    outputs to ``on_out``. Shared by every hopping call site (sync flush,
    pipeline collect, bridge runtimes) — returns the advanced state."""
    from .query_compile import _TS_NEG
    while True:
        hop_next, last_ts = (
            int(v) for v in jax.device_get(
                (state["hop_next"], state["last_ts"])))
        if hop_next <= _TS_NEG or hop_next > last_ts:
            break
        state, out = compiled.step(state, drain_builder.emit())
        on_out(out)
    return state


class DeviceStreamRuntime:
    def __init__(self, app_or_text, batch_capacity: int = 4096,
                 group_capacity: int = 1024, query_index: int = 0,
                 window_capacity: int = 4096):
        app = _parse(app_or_text) if isinstance(app_or_text, str) else app_or_text
        queries = app.queries
        if not queries:
            raise ValueError("no queries in app")
        query = queries[query_index]
        sid = query.input_stream.stream_id
        if sid not in app.stream_definitions:
            raise KeyError(f"stream '{sid}' not defined")
        self.definition = app.stream_definitions[sid]
        self.compiled = CompiledStreamQuery(
            query, self.definition, batch_capacity, group_capacity,
            window_capacity)
        self.builder = BatchBuilder(self.compiled.schema, batch_capacity)
        self.state = self.compiled.init_state()
        self.callback: Optional[Callable[[list[list]], None]] = None
        self._pending_out = []
        # hopping steps host-sync on hop boundaries inside collect(): the
        # pipeline must keep exactly one step in flight (window=1) so the
        # state collect() reads is the dispatched step's own
        self.pipeline_safe = self.compiled.window_kind != "hopping"
        # empty-batch source for hop-boundary drain steps inside collect():
        # the live builder may hold the NEXT batch's staged rows by then
        self._drain_builder = BatchBuilder(self.compiled.schema,
                                           batch_capacity)

    def add_callback(self, fn: Callable[[list[list]], None]) -> None:
        self.callback = fn

    def send(self, row: list, timestamp: int = 0) -> None:
        self.builder.append(row, timestamp)
        if self.builder.full:
            self.flush()

    def flush(self, decode: bool = True) -> None:
        if len(self.builder):
            batch = self.builder.emit()
            self.state, out = self.compiled.step(self.state, batch)
            self._deliver(out, decode)
        if self.compiled.window_kind == "hopping":
            self.state = drain_hop_boundaries(
                self.compiled, self.state, self._drain_builder,
                lambda out: self._deliver(out, decode))

    # -- two-phase step (double-buffered pipeline) ---------------------------
    def dispatch(self, batch: dict):
        """Fire the jitted step without fencing (JAX async dispatch): device
        state advances through donated buffers, the un-fetched output pytree
        is the token ``collect`` later fences at the egress edge."""
        self.state, out = self.compiled.step(self.state, batch)
        return out

    def collect(self, out) -> list[list]:
        """Egress fence + decode for one dispatched step (the np.asarray in
        ``decode_outputs`` blocks until the step completed). Hopping windows
        drain deferred boundary flushes here — pipeline-safe only at
        window=1 (see ``pipeline_safe``)."""
        rows = self.compiled.decode_outputs(out).rows()
        if self.compiled.window_kind == "hopping":
            self.state = drain_hop_boundaries(
                self.compiled, self.state, self._drain_builder,
                lambda o: rows.extend(
                    self.compiled.decode_outputs(o).rows()))
        return rows

    def process(self, batch: dict) -> list[list]:
        return self.collect(self.dispatch(batch))

    def _deliver(self, out, decode: bool) -> None:
        if decode:
            rows = self.compiled.decode_outputs(out).rows()
            if self.callback is not None and rows:
                self.callback(rows)
        else:
            self._pending_out.append(out)

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
