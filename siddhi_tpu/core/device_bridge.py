"""Device execution backend integration: per-query offload with host fallback.

The north star (BASELINE.json): the compiled TPU path plugs in as an execution
backend for individual queries — the role the reference reserves for its
``@Extension``/StreamProcessor plugin boundary — while the host interpreter
remains the fallback (the reference's CPU ``QueryRuntime``).

Usage: annotate a query with ``@device`` (optionally ``@device(batch='4096')``).
The app builder tries the device compiler; on ``DeviceCompileError`` the query
silently builds on the host path instead (``@device(strict='true')`` raises).
Events route into a micro-batching bridge; device outputs flow back into the
target junction as CURRENT events. Batching semantics: outputs surface when a
micro-batch fills or on ``SiddhiAppRuntime.flush_device()`` (also invoked by
playback watermark advancement).
"""

from __future__ import annotations

import logging
import time
from typing import Optional

from ..query_api import (
    InsertIntoStream,
    JoinInputStream,
    OutputEventsFor,
    Query,
    SingleInputStream,
    StateInputStream,
)
from ..query_api.annotation import find_annotation
from ..observability.phases import span_name
from ..observability.profiler import span
from .egress import ChunkEgress
from .event import EventType, StreamEvent

log = logging.getLogger("siddhi_tpu.device")


class AsyncDeviceDriver:
    """Double-buffered async device pipeline: pack ∥ step ∥ emit.

    The VERDICT-named analog of the reference's ``@async`` Disruptor mode for
    the device path (``StreamJunction.java:279-316``), rebuilt as a software
    pipeline. Three edges, one FIFO:

    - **pack** (producer, engine lock held): the junction thread packs events
      into the runtime's staging builder; emitted batches enter this driver's
      bounded ring (``depth``);
    - **dispatch** (worker): ``rt.dispatch(batch)`` fires the jitted step and
      returns an UN-FENCED output token — JAX async dispatch returns while
      the device still computes, and the carried state round-trips through
      donated buffers (``jax.jit(..., donate_argnums=(0,))``), so dispatch is
      fire-and-forget;
    - **egress** (worker): ``rt.collect(token)`` fences (the only host sync
      on the path) and decodes into one ``ColumnsOut`` chunk, which is
      then delivered whole under the engine lock.

    Each edge is a span on the profiler's clock and a phase tracker, split
    where the waits are (``observability/profiler.py``, ``phases.py``): the
    producer's wait on a full ring, dispatch, fence against decode inside
    ``collect``, the wait for the engine lock against the publishing.

    With ``window=2`` (double buffering) the worker keeps one dispatch in
    flight while fencing the previous token: the device computes batch N
    while the host decodes batch N−1 and the producer packs batch N+1.
    Tokens collect strictly FIFO, so a mid-pipeline device fault surfaces at
    its own egress slot — the DeviceGuard replays the failed batch's shadow
    there, after every earlier batch delivered, and can neither reorder nor
    double-emit a micro-batch. A batch the runtime's dispatch marked
    ``_serial`` (its collect reads live state back: a hopping window whose
    step may have deferred a boundary) is the last one in flight until it
    is collected, as with ``window=1``.

    A latency-mode adaptive controller (``@app:adaptive(latency.target.ms)``)
    adds a **deadline flush**: when the pipeline idles with a partial batch
    staged longer than the controller's remaining latency budget, the worker
    flushes it — detection latency stays bounded by ~fill-wait + one step
    instead of waiting for capacity.
    """

    def __init__(self, rt, app_context, depth: int = 4, window: int = 2):
        import collections
        import threading
        self.rt = rt
        self.app_context = app_context
        self.depth = max(1, depth)
        # in-flight dispatch window: 2 = double buffering (a serial batch
        # closes it until it is collected: _next_action)
        self.window = max(1, window)
        self._q = collections.deque()            # packed, undispatched
        self._inflight = collections.deque()     # (batch, token, t_disp0,
        # disp_s, disp_cpu_s, err)
        self._cv = threading.Condition()
        self._busy = False          # dispatch/collect/delivery in flight
        self._paused = False
        self._stopped = False
        self.batches_stepped = 0
        self.step_seconds = 0.0     # cumulative dispatch + collect time
        q = rt.query_name
        # the segments' spans (phases.SEGMENTS), and the two wholes
        self._spans = {p: span_name(p, q) for p in (
            "ring_wait", "device_step", "lock_wait", "sink_publish")}
        self._spans.update({call: f"siddhi:{call}:{q}"
                            for call in ("collect", "deliver")})
        # counter-check cadence under sustained load: on_drained normally
        # runs when the pipeline empties, but a saturated pipeline never
        # empties — force the bookkeeping every N collected batches (one
        # amortized fence per N steps) so overflow warnings still surface
        self.drain_check_every = 64
        self._since_drained = 0
        self._thread = threading.Thread(
            target=self._run, name="device-driver", daemon=True)
        self._thread.start()

    # -- producer side (engine lock held) ------------------------------------
    def submit(self, batch) -> None:
        with self._cv:
            # backpressure without deadlock: the producer usually holds the
            # engine lock the delivery path needs, so a full queue waits
            # briefly then grows (bounded in practice by the wait)
            if len(self._q) >= self.depth:
                t0 = time.perf_counter()
                with span(self._spans["ring_wait"]):
                    self._cv.wait(timeout=0.2)
                batch["_ring_wait_s"] = time.perf_counter() - t0
            self._q.append(batch)
            self._cv.notify_all()

    # -- introspection --------------------------------------------------------
    @property
    def pipeline_depth(self) -> int:
        """Batches in the driver: packed-but-undispatched + in flight."""
        return len(self._q) + len(self._inflight)

    # -- worker ---------------------------------------------------------------
    def _run(self) -> None:
        # this thread's CPU clock as the last batch last read it (behind
        # its collect, or its publishing): the difference from batch to
        # batch is ``driver_cpu``
        self._cpu_mark = time.thread_time()
        while True:
            action, batch = self._next_action()
            if action == "stop":
                return
            if action == "dispatch":
                self._dispatch(batch)
            elif action == "collect":
                self._collect_oldest()
            elif action == "drained":
                self._run_drained_checks()
            else:                       # 'deadline'
                self._deadline_flush()

    def _run_drained_checks(self) -> None:
        """Deferred host-sync bookkeeping (counter checks need device_get)
        — OUTSIDE the condition variable: producers blocked in submit()
        hold the engine lock, and a d2h fetch under _cv would freeze
        ingress for its whole round-trip."""
        self._since_drained = 0
        try:
            self.rt.on_drained()
        except Exception:   # noqa: BLE001 — bookkeeping must not kill the
            # sole device worker
            log.exception("on_drained failed")

    def _next_action(self):
        with self._cv:
            while True:
                if self._q and not self._paused \
                        and len(self._inflight) < self.window \
                        and not (self._inflight
                                 and self._inflight[-1][0].get("_serial")):
                    self._busy = True
                    return "dispatch", self._q.popleft()
                if self._inflight:
                    # window full or closed by a serial batch, paused, or
                    # queue empty: fence the oldest token (strict FIFO
                    # egress)
                    return "collect", None
                # pipeline drained: idle-wait (the drained bookkeeping runs
                # in _run, outside this lock)
                if self._busy:
                    self._busy = False
                    self._cv.notify_all()
                    return "drained", None
                if self._stopped:
                    return "stop", None
                wait_s = 0.5
                if not self._paused and self._builder_staging():
                    due_in = self._deadline_due_in_s()
                    if due_in is not None and due_in <= 0.0:
                        return "deadline", None
                    if due_in is not None:
                        wait_s = min(wait_s, max(due_in, 0.001))
                self._cv.wait(timeout=wait_s)

    def _builder_staging(self) -> bool:
        """Rows staged in the producer's builder while the worker idles."""
        try:
            return len(self.rt.builder) > 0
        except Exception:   # noqa: BLE001 — advisory read without the lock
            return False

    def _deadline_ms(self):
        """Wall-clock flush deadline for partial batches, or None when no
        latency-mode controller is attached."""
        c = self.rt.batch_controller
        if c is None or c.mode != "latency":
            return None
        if not self._builder_staging():
            return None
        return c.flush_deadline_ms

    def _deadline_due_in_s(self):
        deadline_ms = self._deadline_ms()
        if deadline_ms is None:
            return None
        t0 = getattr(self.rt.builder, "_pack_t0", None)
        if t0 is None:
            return None
        return deadline_ms / 1e3 - (time.perf_counter() - t0)

    def _deadline_flush(self) -> None:
        """Flush a partial batch whose staging age exceeded the latency
        budget (worker thread, takes the engine lock like any producer)."""
        with self.app_context.root_lock:
            due = self._deadline_due_in_s()
            if due is None or due > 0.0:
                return      # raced with a producer flush — nothing to do
            self.rt._count_flush("deadline")
            # the runtime's own flush: seal + emit + driver submit, so the
            # deadline path can never diverge from producer-side flushes
            self.rt.flush()

    def _dispatch(self, batch) -> None:
        t0, c0 = time.perf_counter(), time.thread_time()
        err = None
        token = None
        try:
            with span(self._spans["device_step"]):
                token = self.rt.dispatch(batch)
        except Exception as e:  # noqa: BLE001 — without a DeviceGuard
            # installed a dispatch failure must not kill the worker; the
            # batch is consumed (counted at its egress slot)
            log.exception("device dispatch failed")
            err = e
        disp_s, disp_cpu_s = time.perf_counter() - t0, time.thread_time() - c0
        with self._cv:
            self._inflight.append((batch, token, t0, disp_s, disp_cpu_s, err))
            self._cv.notify_all()

    def _collect_oldest(self) -> None:
        with self._cv:
            batch, token, t_disp0, disp_s, disp_cpu_s, err = \
                self._inflight.popleft()
        rt = self.rt
        t0, c0 = time.perf_counter(), time.thread_time()
        rows = []
        ok = False
        try:
            if err is None:
                with span(self._spans["collect"]):
                    rows = rt.collect(token)
                ok = True
        except Exception:   # noqa: BLE001 — an async-dispatched step's
            # failure surfaces at the fence; with the resilience layer
            # active the DeviceGuard has already rerouted the batch to the
            # host path before this can trigger
            log.exception("device step failed")
            rows = []
        cpu_now = time.thread_time()
        collect_s, collect_cpu_s = time.perf_counter() - t0, cpu_now - c0
        dt = disp_s + collect_s
        self.step_seconds += dt
        self.batches_stepped += 1
        lock_s = publish_s = publish_cpu_s = 0.0
        if rows:
            lock = self.app_context.root_lock
            tp0 = time.perf_counter()
            with span(self._spans["deliver"]):
                with span(self._spans["lock_wait"]):
                    lock.acquire()
                tp1, cp1 = time.perf_counter(), time.thread_time()
                try:
                    # stamp outputs with the batch's own last event time —
                    # the producer-side _out_ts has already advanced to
                    # newer events by delivery time
                    with span(self._spans["sink_publish"]):
                        rt.deliver(rows, batch.get("last_ts"))
                except Exception:   # noqa: BLE001 — a raising downstream
                    # receiver must not kill the sole device worker, and the
                    # probe below must still see this batch (FIFO trace
                    # groups)
                    log.exception("device delivery failed")
                finally:
                    lock.release()
            lock_s = tp1 - tp0
            publish_s = time.perf_counter() - tp1
            cpu_now = time.thread_time()
            publish_cpu_s = cpu_now - cp1
        try:
            # the probe must see EVERY consumed batch (success or not) or
            # its FIFO trace groups desynchronize; observed AFTER delivery
            # so the phase attribution covers the whole serial waterfall
            # (fill → pack → ring wait → queue → dispatch → fence → decode
            # → lock wait → publish)
            t_emit = batch.get("_t_emit")
            queue_s = max(0.0, t_disp0 - t_emit) \
                if t_emit is not None else 0.0
            queue_s += max(0.0, t0 - (t_disp0 + disp_s))
            ring_s = batch.get("_ring_wait_s", 0.0)
            # all this thread's CPU since the last batch's last reading:
            # the recording below, on_drained and _next_action included
            mark, self._cpu_mark = self._cpu_mark, cpu_now
            rt.observe_step(
                batch.get("count", 0), dt, device_path=ok,
                phases=rt.step_phases(
                    batch, queue_s=max(0.0, queue_s - ring_s),
                    step_s=disp_s, step_cpu_s=disp_cpu_s,
                    collect_s=collect_s, collect_cpu_s=collect_cpu_s,
                    ring_s=ring_s, lock_s=lock_s, publish_s=publish_s,
                    publish_cpu_s=publish_cpu_s,
                    driver_cpu_s=self._cpu_mark - mark))
        except Exception:   # noqa: BLE001 — a raising observer must not
            # kill the sole device worker
            log.exception("step observer failed")
        self._since_drained += 1
        if self._since_drained >= self.drain_check_every:
            # sustained load never drains the pipeline: run the overflow
            # checks anyway (costs one fence per drain_check_every steps)
            self._run_drained_checks()
        with self._cv:
            self._cv.notify_all()

    # -- barriers --------------------------------------------------------------
    def quiesce(self, timeout: float = 60.0) -> bool:
        """Wait until the ring is empty and no dispatch, fence, or delivery
        is in flight. Must NOT be called while holding the engine lock (the
        worker's egress edge needs it)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._q or self._inflight or self._busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(timeout=min(remaining, 0.5))
        return True

    def snapshot_staged(self) -> list:
        """Queued-but-unstepped batches (numpy dicts) for checkpointing the
        receive-but-not-process gap. Call with the driver paused."""
        with self._cv:
            return list(self._q)

    def restore_staged(self, batches: list) -> None:
        with self._cv:
            self._q.clear()
            self._q.extend(batches)
            self._cv.notify_all()

    def flush_sync(self, cause=None) -> None:
        """Submit any partial batch and drain: device state reflects every
        event sent so far. Call without the engine lock. ``cause`` counts
        the flush and stamps the batch UNDER the lock — cause bookkeeping
        is single-slot, so it must not race producer-side flushes."""
        with self.app_context.root_lock:
            if len(self.rt.builder):
                if cause is not None:
                    self.rt._count_flush(cause)
                self.submit(self.rt._emit_batch())
        self.quiesce()

    def pause(self) -> None:
        """Freeze device-state mutation (snapshot walks read ``rt.state``).
        Waits for the whole in-flight cycle — step AND delivery — so a
        snapshot can't capture device state advanced past rows downstream
        hasn't seen. Must not be called holding the engine lock."""
        with self._cv:
            self._paused = True
            while self._busy:
                self._cv.wait(timeout=0.5)

    def resume(self) -> None:
        with self._cv:
            self._paused = False
            self._cv.notify_all()

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._paused = False
            self._cv.notify_all()
        self._thread.join(timeout=10.0)


class _LimiterSink:
    """Terminal processor behind the bridge's host-side rate limiter."""

    def __init__(self, bridge: "DeviceQueryBridge"):
        self.bridge = bridge

    def process(self, events: list[StreamEvent]) -> None:
        self.bridge._publish_events(events)


class DeviceQueryBridge(ChunkEgress):
    """Junction subscriber feeding a compiled device query; outputs re-enter the
    engine through the query's output junction, a batch's chunk at a time
    (``ChunkEgress``): as columns when every subscriber takes columns, else
    as one chunk of events.

    Output rate limiting (``output [all|first|last] every ...`` /
    ``output snapshot``) runs host-side on the decoded device rows — the
    limiters are sequential post-selector processors in the reference
    (``query/output/ratelimit/OutputRateLimiter.java:43``) and their
    semantics don't depend on chunking, so the same host classes apply
    verbatim after device decode. Device-emitted events carry the batch
    timestamp, so time-driven limiters key off that (documented divergence
    from per-event host timestamps, consistent with the device path's
    output stamping)."""

    def __init__(self, kind: str, runtime, app_context, stream_ids: list[str],
                 output_junction, query_name: str, async_mode: bool = False,
                 output_rate=None, pipeline_window: int = 2):
        self.kind = kind        # 'stream' | 'nfa' | 'join' | 'partition'
        self.runtime = runtime  # the kind's StepRuntime (tpu/step_runtime.py)
        self.app_context = app_context
        self.stream_ids = stream_ids
        self.output_junction = output_junction
        self.query_name = query_name
        self.query_callbacks: list = []
        self.guard = None                   # DeviceGuard (resilience layer)
        self.probe = None                   # DeviceStepProbe (observability)
        self._init_egress()
        runtime.query_name = query_name     # the profiler spans' <query>
        runtime.callback = self._on_chunk   # deliver()'s fn(chunk, emit_ts)
        self._out_ts = 0
        self.rate_limiter = None
        if output_rate is not None:
            from .ratelimit import build_rate_limiter
            self.rate_limiter = build_rate_limiter(output_rate, app_context)
            self.rate_limiter.next = _LimiterSink(self)
        self.driver = None
        if async_mode:
            self.driver = AsyncDeviceDriver(runtime, app_context,
                                            window=pipeline_window)
            runtime.driver = self.driver

    # -- junction receiver(s) -------------------------------------------------
    def receiver_for(self, stream_id: str):
        bridge = self

        class _R:
            def receive(self, event: StreamEvent) -> None:
                bridge.on_event(stream_id, event)

        if self.kind in ("stream", "partition") \
                and hasattr(self.runtime, "send_columns"):
            # single-stream device queries take columnar chunks straight
            # into the staging BatchBuilder (append_columns — bulk
            # slice-copy, no per-event appends): the last per-event hop on
            # the DCN-ingest → device path the mesh fabric forwards over.
            # A served partition routes one stream and takes them the same
            # way. Merged (nfa/join) builders stay per-event by design —
            # their probe/trace FIFO is stamped per interleaved stream event.
            class _ColsR(_R):
                def receive_rows(self, rows: list, timestamps) -> None:
                    bridge.on_rows_chunk(stream_id, rows, timestamps)

                def receive_columns(self, cols: dict, ts, n: int) -> None:
                    bridge.on_columns_chunk(stream_id, cols, ts, n)

            return _ColsR()
        return _R()

    def on_event(self, stream_id: str, event: StreamEvent) -> None:
        if event.type != EventType.CURRENT:
            return
        probe = self.probe
        if probe is not None and probe.tracer is not None:
            # register BEFORE packing: a capacity flush inside send() steps
            # the batch this event is part of, closing the span right away
            tr = probe.tracer.active
            if tr is not None:
                probe.pending.append((tr, time.perf_counter_ns()))
        self._out_ts = event.timestamp
        if self.kind == "stream":
            self.runtime.send(event.data, timestamp=event.timestamp)
        else:                       # 'nfa' | 'join': merged multi-stream batch
            self.runtime.send(stream_id, event.data, event.timestamp)

    def _register_chunk_trace(self) -> None:
        """One pending probe-trace entry per CHUNK (the fleet stager's
        convention) — a chunk's events share one journey, and per-event
        registration is exactly the hop this path exists to remove."""
        probe = self.probe
        if probe is not None and probe.tracer is not None:
            tr = probe.tracer.active
            if tr is not None:
                probe.pending.append((tr, time.perf_counter_ns()))

    def on_rows_chunk(self, stream_id: str, rows: list, timestamps) -> None:
        """Zero-wrap row-chunk ingress (``deliver_rows``): no StreamEvent
        materialization, one trace registration per chunk."""
        self._register_chunk_trace()
        send = self.runtime.send
        if self.kind == "stream":
            for row, ts in zip(rows, timestamps):
                send(row, timestamp=ts)
        else:
            for row, ts in zip(rows, timestamps):
                send(stream_id, row, ts)
        if timestamps:
            self._out_ts = timestamps[-1]

    def on_columns_chunk(self, stream_id: str, cols: dict, ts,
                         n: int) -> None:
        """Zero-object columnar ingress (``deliver_columns``): the chunk
        bulk-slice-copies into the staging builder via
        ``BatchBuilder.append_columns`` — no per-event appends at all."""
        if n == 0:
            return
        self._register_chunk_trace()
        self.runtime.send_columns(cols, ts)
        self._out_ts = int(ts[-1])

    def flush(self, cause: str = "drain") -> None:
        if self.driver is not None:
            # async: submit the partial batch and drain the device queue.
            # Must not hold the engine lock here (the worker's delivery
            # needs it); the cause is counted inside flush_sync UNDER the
            # lock so concurrent producer/deadline flushes can't swap the
            # single-slot pending cause
            self.driver.flush_sync(cause)
            return
        with self.app_context.root_lock:
            if len(self.runtime.builder):
                self.runtime._count_flush(cause)
            self.runtime.flush()

    def finalize(self) -> None:
        """Shutdown barrier: emit what an open device segment still holds
        (timeBatch's terminal bucket)."""
        self.flush(cause="final")
        self.runtime.finalize()
        if self.driver is not None:
            self.driver.flush_sync()

    def _on_chunk(self, out, emit_ts=None) -> None:
        """One batch's output chunk, every row stamped with the source
        batch's last event time (async delivery passes it; the producer-side
        ``_out_ts`` may already have advanced past it)."""
        out.stamp(self._out_ts if emit_ts is None else emit_ts)
        self._on_out(out)


def _input_single_streams(ist) -> list[SingleInputStream]:
    """Every SingleInputStream reachable from a query input (join sides,
    pattern/sequence stream elements) — for whole-surface audits."""
    out: list[SingleInputStream] = []
    if isinstance(ist, SingleInputStream):
        out.append(ist)
    elif isinstance(ist, JoinInputStream):
        out.extend([ist.left, ist.right])
    elif isinstance(ist, StateInputStream):
        out.extend(ist.single_streams())
    return out


def _device_options(ann, query: Query, stream_defs: dict) -> dict:
    """``@device(...)`` as the builders read it. ``async`` is the explicit
    key or any input stream annotated ``@async`` (the reference's Disruptor
    opt-in): packing then overlaps the step."""
    async_mode = (ann.get("async") or "false").lower() == "true"
    if not async_mode:
        ist = query.input_stream
        sids = [s.stream_id for s in _input_single_streams(ist)
                if getattr(s, "stream_id", None) is not None]
        async_mode = any(
            find_annotation(stream_defs[sid].annotations, "async") is not None
            for sid in sids if sid in stream_defs)
    return {
        "strict": (ann.get("strict") or "false").lower() == "true",
        "batch": int(ann.get("batch") or 1024),
        "slots": int(ann.get("slots") or 64),
        "window": int(ann.get("window") or 4096),
        # in-flight dispatch window of the async pipeline (2 = double
        # buffering; 1 = serialize dispatch/egress, for A/B comparison)
        "pipeline": int(ann.get("pipeline") or 2),
        # key lanes of a served `partition with` block
        "lanes": int(ann.get("lanes") or 64),
        # keys a served keyed window holds a window for (its table's rows)
        "keys": int(ann.get("keys") or 65536),
        "async": async_mode,
    }


def _audit_device_surface(query: Query, app_context, get_junction):
    """The full Query-surface audit: anything the device compilers do not
    model raises ``DeviceCompileError`` (-> host fallback), never silently
    drops semantics (reference surface: Query.java — selector
    order-by/limit/offset QuerySelector.java:44, served on a grouped
    hopping flush only, ``query_compile.selector_tail_refusal``; output_rate
    OutputRateLimiter.java:43, fault/inner streams, events_for). Returns
    the output junction."""
    from ..tpu.expr_compile import DeviceCompileError

    sel = query.selector
    # order by / limit / offset apply to a CHUNK: served where the window
    # flushes chunks of its own and is compiled with the tail (a grouped
    # hopping flush); everywhere else the refusal says which
    from ..tpu.query_compile import selector_tail_refusal
    refusal = selector_tail_refusal(query)
    if refusal is not None:
        raise DeviceCompileError(refusal)
    if query.output_rate is not None:
        from ..query_api import EventOutputRate
        if not isinstance(query.output_rate, EventOutputRate):
            # time/snapshot limiters key off per-event output timestamps,
            # which device batching coarsens to the batch timestamp —
            # host fallback preserves exact semantics
            raise DeviceCompileError(
                "time/snapshot output rate limiting takes the host path")
        if isinstance(query.input_stream, JoinInputStream):
            # host join selectors can feed EXPIRED events into the
            # limiter; the device join emits CURRENT rows only
            raise DeviceCompileError(
                "output rate limiting on joins takes the host path")
        from ..query_api import OutputRateType
        if sel is not None and sel.group_by and \
                query.output_rate.type in (OutputRateType.FIRST,
                                           OutputRateType.LAST):
            # grouped first/last emit PER KEY per batch (reference
            # FirstGroupByPerEventOutputRateLimiter); device rows do
            # not carry group keys through the limiter
            raise DeviceCompileError(
                "group-by with first/last output rate limiting takes "
                "the host path")
    if not isinstance(query.output_stream, InsertIntoStream):
        raise DeviceCompileError(
            "device path handles insert-into-stream outputs only")
    if query.output_stream.events_for != OutputEventsFor.CURRENT_EVENTS:
        raise DeviceCompileError(
            "insert into ... for expired/all events takes the host path "
            "(device kernels emit CURRENT rows only)")
    if query.output_stream.is_fault_stream:
        raise DeviceCompileError("fault-stream outputs take the host path")
    for s in _input_single_streams(query.input_stream):
        if s.is_fault_stream or s.is_inner_stream:
            raise DeviceCompileError(
                "fault / partition-inner input streams take the host "
                "path")
    tid = query.output_stream.target_id
    if tid in app_context.tables or tid in app_context.named_windows:
        raise DeviceCompileError(
            f"device path cannot target table/window '{tid}'")
    return get_junction(tid, query.output_stream.is_inner_stream)


def _finish_bridge(bridge: "DeviceQueryBridge", element, name: str,
                   app_context, stream_defs: dict, get_junction,
                   batch: int) -> "DeviceQueryBridge":
    """What every device bridge gets once its runtime compiled: the
    adaptive controller, the DeviceGuard, the snapshot registration.
    ``element`` is what a guard replays through on the host: the query, or
    the whole ``Partition`` of a served partition."""
    rt = bridge.runtime
    bridge.batch_capacity = batch       # pad-ratio denominator (observability)
    if app_context.adaptive_cfg is not None:
        # @app:adaptive: flush thresholds track observed rate/latency; the
        # query's own batch capacity caps the adjustable range
        from ..flow.adaptive_batch import AdaptiveBatchController
        cfg = dict(app_context.adaptive_cfg)
        cfg["max_batch"] = min(cfg.get("max_batch", batch), batch)
        cfg["min_batch"] = min(cfg.get("min_batch", 64), cfg["max_batch"])
        rt.batch_controller = AdaptiveBatchController(**cfg)
    # device quarantine: a RUNTIME step failure (compile-time failures fell
    # back before) reroutes the batch through the host interpreter, and
    # repeated failures circuit-break the device path itself
    resilience = getattr(app_context.runtime, "resilience", None)
    if resilience is not None:
        bridge.guard = resilience.guard_device(
            rt, element, name, dict(stream_defs), get_junction, bridge.kind)
        resilience.bind_bridge(bridge.guard, bridge)
    app_context.register_state(f"device-{name}", _BridgeState(bridge))
    return bridge


def try_build_device_query(query: Query, app_context, stream_defs: dict,
                           get_junction, name: str) -> Optional[DeviceQueryBridge]:
    """Returns a bridge when the query opts in via @device AND compiles on the
    device path; None → caller builds the host runtime."""
    ann = find_annotation(query.annotations, "device")
    if ann is None:
        return None
    opts = _device_options(ann, query, stream_defs)
    strict, batch, slots = opts["strict"], opts["batch"], opts["slots"]
    window_cap, pipeline_window = opts["window"], opts["pipeline"]
    async_mode = opts["async"]

    from ..tpu.expr_compile import DeviceCompileError

    try:
        target = _audit_device_surface(query, app_context, get_junction)
        ist = query.input_stream
        if isinstance(ist, SingleInputStream):
            from ..tpu.query_compile import CompiledStreamQuery
            from ..tpu.runtime import DeviceStreamRuntime

            d = stream_defs.get(ist.stream_id)
            if d is None:
                raise DeviceCompileError(f"undefined stream '{ist.stream_id}'")
            compiled = CompiledStreamQuery(query, d, batch_capacity=batch,
                                           window_capacity=window_cap)
            if query.output_rate is not None and \
                    compiled.window_kind is not None:
                # host rate limiters count the window's EXPIRED events too
                # (selector → limiter → events_for filter); device kernels
                # emit CURRENT rows only, so the counts would diverge
                raise DeviceCompileError(
                    "output rate limiting on windowed queries takes the "
                    "host path")
            rt = DeviceStreamRuntime(compiled=compiled)
            bridge = DeviceQueryBridge("stream", rt, app_context,
                                       [ist.stream_id], target, name,
                                       async_mode=async_mode,
                                       output_rate=query.output_rate,
                                       pipeline_window=pipeline_window)
            bridge.output_schema = ([s.name for s in compiled.specs],
                                    [s.dtype for s in compiled.specs])
        elif isinstance(ist, StateInputStream):
            from ..tpu.nfa import DeviceNFACompiler, DeviceNFARuntime

            compiler = DeviceNFACompiler(query, stream_defs, slots, batch)
            # absent-start seeds arm their clock at the app's start time
            # (host: seed placed at start() on the playback clock)
            rt = DeviceNFARuntime(compiler=compiler,
                                  start_time=app_context.current_time())
            bridge = DeviceQueryBridge("nfa", rt, app_context,
                                       compiler.compiled.stream_ids, target,
                                       name, async_mode=async_mode,
                                       output_rate=query.output_rate,
                                       pipeline_window=pipeline_window)
            bridge.output_schema = ([n for n, _, _ in compiler.out_specs],
                                    [t for _, _, t in compiler.out_specs])
        elif isinstance(ist, JoinInputStream):
            from ..tpu.join_compile import (
                CompiledJoinQuery,
                DeviceJoinRuntime,
            )

            ring = int(ann.get("ring") or 1024)
            joined = int(ann.get("joined") or 2048)
            compiled = CompiledJoinQuery(
                query, dict(stream_defs), batch_capacity=batch,
                ring_capacity=ring, joined_capacity=joined)
            rt = DeviceJoinRuntime(compiled=compiled)
            bridge = DeviceQueryBridge(
                "join", rt, app_context,
                [compiled.left_id, compiled.right_id], target, name,
                async_mode=async_mode, output_rate=query.output_rate,
                pipeline_window=pipeline_window)
            bridge.output_schema = ([n for (n, _, t, _) in compiled.out_specs],
                                    [t for (n, _, t, _) in compiled.out_specs])
        else:
            raise DeviceCompileError(
                "device path covers single-stream, pattern/sequence, and "
                "windowed stream-join inputs")
    except DeviceCompileError as e:
        if strict:
            raise
        log.info("query '%s' falls back to host path: %s", name, e)
        return None

    return _finish_bridge(bridge, query, name, app_context, stream_defs,
                          get_junction, batch)


def try_build_device_partition(partition_ast, app_context, stream_defs: dict,
                               get_junction,
                               name: str) -> Optional[DeviceQueryBridge]:
    """The device branch of a ``partition with`` block: ONE bridge (kind
    ``'partition'``) when an inner query opts in via ``@device`` and the
    block is one value partition ``partition with (<attr> of <Stream>)``
    holding one query the chip serves:

    - a pattern the device NFA compiler takes, over a served
      ``PartitionedNFARuntime``: keys hash to ``@device(lanes=)``
      lane-stacked match tables and every lane steps in one vmapped
      program, the blocked kernel for a chain of stream states under
      ``every``, the per-event scan for count (``<m:n>``, a Kleene
      closure), logical and absent states;
    - a single-stream query over a sliding ``window.length(N)`` with
      aggregates (``sum``, ``count``, ``avg``, ``min``, ``max``, a filter,
      ``having``, the current event's columns), over a
      ``tpu/keyed_window.py`` ``KeyedWindowRuntime``: every key's window
      one row of one table of ``@device(keys=)`` rows, the keys given
      stable slots on the host.

    What still keeps the caller's tiers (fleet, host partition, per-key
    interpreter; None here, a raise under ``strict='true'``): sequences
    (strictness is per key), first states that bind no alias, several
    queries in the block, multi-stream, range or expression partitions,
    output rate limiting, joins, a keyed query with another window or
    none, with group-by, ``stdDev`` or no aggregate, a key of a FLOAT or
    DOUBLE attribute, and whatever the NFA compiler itself refuses."""
    from ..query_api import Variable
    from ..tpu.expr_compile import DeviceCompileError

    anns = [find_annotation(q.annotations, "device")
            for q in partition_ast.queries]
    ann = next((a for a in anns if a is not None), None)
    if ann is None:
        return None
    query = partition_ast.queries[anns.index(ann)]
    opts = _device_options(ann, query, stream_defs)
    batch = opts["batch"]
    try:
        if len(partition_ast.queries) != 1:
            raise DeviceCompileError(
                "a partition of several queries keeps the host tiers (one "
                "lane-stacked program holds one pattern)")
        if len(partition_ast.partition_types) != 1:
            raise DeviceCompileError(
                "multi-stream partitions keep the host tiers")
        pt = partition_ast.partition_types[0]
        if getattr(pt, "value_expr", None) is None or \
                not isinstance(pt.value_expr, Variable) or \
                pt.value_expr.stream_index is not None:
            raise DeviceCompileError(
                "range/expression partitions keep the host tiers")
        ist = query.input_stream
        if not isinstance(ist, (StateInputStream, SingleInputStream)):
            raise DeviceCompileError(
                "joins in a partition keep the host tiers")
        if isinstance(ist, SingleInputStream) and \
                ist.stream_id != pt.stream_id:
            raise DeviceCompileError(
                "a partition query over a stream the block does not key "
                "keeps the host tiers")
        if query.output_rate is not None:
            raise DeviceCompileError(
                "output rate limiting in a partition is per key (host "
                "tiers)")
        target = _audit_device_surface(query, app_context, get_junction)
        if isinstance(ist, StateInputStream):
            from ..tpu.partition import PartitionedNFARuntime
            rt = PartitionedNFARuntime(
                None, opts["lanes"], pt.value_expr.attribute,
                slot_capacity=opts["slots"], batch=batch, query=query,
                stream_defs=stream_defs)
            stream_ids = rt.compiler.compiled.stream_ids
            out_specs = rt.compiler.out_specs
        else:
            from ..tpu.keyed_window import KeyedWindowRuntime
            rt = KeyedWindowRuntime(query, stream_defs,
                                    pt.value_expr.attribute, batch,
                                    opts["keys"])
            stream_ids = [ist.stream_id]
            out_specs = rt.out_specs
    except DeviceCompileError as e:
        if opts["strict"]:
            raise
        log.info("partition '%s' falls back to the host tiers: %s", name, e)
        return None
    qname = query.name() or f"{name}-query-0"
    bridge = DeviceQueryBridge(
        "partition", rt, app_context, stream_ids, target, qname,
        async_mode=opts["async"], pipeline_window=opts["pipeline"])
    bridge.output_schema = ([n for n, _, _ in out_specs],
                            [t for _, _, t in out_specs])
    return _finish_bridge(bridge, partition_ast, qname, app_context,
                          stream_defs, get_junction, batch)


class _BridgeState:
    """Snapshot adapter: device state pytree is host-fetchable."""

    def __init__(self, bridge: DeviceQueryBridge):
        self.bridge = bridge

    def snapshot_state(self):
        limiter = self.bridge.rate_limiter
        if self.bridge.driver is None:
            self.bridge.flush()
            st = self.bridge.runtime.snapshot_state()
            if limiter is None:
                return st
            return {"rt": st, "limiter": limiter.snapshot_state()}
        # async mode: SiddhiAppRuntime._pre_snapshot already flushed + paused
        # the driver (flushing here would deadlock — we hold root_lock and
        # the worker's delivery phase needs it). Events that raced in between
        # the pre-drain and this lock acquisition sit in the builder / driver
        # queue — checkpoint them as staged batches so the cut is consistent
        # with the host-side state walked under the same lock.
        st = {
            "rt": self.bridge.runtime.snapshot_state(),
            "staged": self.bridge.driver.snapshot_staged(),
            "builder": self.bridge.runtime.builder.snapshot(),
        }
        if limiter is not None:
            st["limiter"] = limiter.snapshot_state()
        return st

    def restore_state(self, state):
        if isinstance(state, dict) and "rt" in state:
            if self.bridge.rate_limiter is not None and "limiter" in state:
                self.bridge.rate_limiter.restore_state(state["limiter"])
            if "staged" not in state:       # sync-mode shape with a limiter
                self.bridge.runtime.restore_state(state["rt"])
                return
            # async-mode snapshot shape — also restorable into a runtime
            # whose async opt-in was removed: staged batches are stepped
            # synchronously instead of re-queued
            self.bridge.runtime.restore_state(state["rt"])
            self.bridge.runtime.builder.restore(state["builder"])
            if self.bridge.driver is not None:
                self.bridge.driver.restore_staged(state["staged"])
            else:
                rt = self.bridge.runtime
                for batch in state["staged"]:
                    rt.deliver(rt.process(batch), batch.get("last_ts"))
            return
        self.bridge.runtime.restore_state(state)
