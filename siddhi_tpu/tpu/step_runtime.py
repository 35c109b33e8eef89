"""The served step's protocol, written once.

A batch's life on the served path is seal → dispatch → fence → decode →
deliver, with a flush rule in front and a ``phases`` record behind.
:class:`StepRuntime` owns all of it; a runtime supplies what differs between
plans: a ``builder`` to stage into, ``dispatch(batch)`` (fire the jitted step,
return its un-fenced outputs), ``_decode(out)`` (those outputs as one
``ColumnsOut``) and ``fence_key`` (the output the decode reads first). The
five device runtimes subclass it beside their compilers
(``DeviceStreamRuntime``, ``DeviceNFARuntime``, ``DeviceJoinRuntime``,
``PartitionedNFARuntime``, ``KeyedWindowRuntime``); the columnar host tier
(``core/host_bridge.py``) inherits the flush rule and the cause bookkeeping
and times its one-segment step itself.

What plugs in from outside: ``batch_controller`` (``flow/adaptive_batch.py``,
through ``@app:adaptive``), ``step_observer`` / ``step_sealer`` /
``flush_causes`` / ``flight`` (``observability``), ``driver`` and ``callback``
(``core/device_bridge.py``). A ``DeviceGuard`` wraps ``dispatch`` and
``collect`` on the instance (``resilience/device_guard.py``), so ``process``
resolves both through ``self``.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from ..observability.phases import SEGMENTS, span_name
from ..observability.profiler import span


class _Measured:
    """``StepRuntime.measure``'s context: the clocks read outside the span,
    as a ``with`` round them would; ``end`` is the wall clock at the close.
    A stretch that raises files nothing."""

    def __init__(self, parts: dict, name: str, label: Optional[str]):
        self.parts, self.name, self.label = parts, name, label
        self.cpu = SEGMENTS[name].cpu

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time() if self.cpu else 0.0
        if self.label:
            self.span = span(self.label)
            self.span.__enter__()
        return self

    def __exit__(self, *exc):
        if self.label:
            self.span.__exit__(*exc)
        if exc[0] is None:
            self.end, parts = time.perf_counter(), self.parts
            parts[self.name] = parts.get(self.name, 0.0) + self.end - self.t0
            if self.cpu:
                parts[self.cpu] = parts.get(self.cpu, 0.0) \
                    + time.thread_time() - self.c0


class StepRuntime:
    batch_controller = None     # AdaptiveBatchController via @app:adaptive
    step_observer = None        # DeviceStepProbe.on_step (observability)
    step_sealer = None          # DeviceStepProbe.seal — closes the probe's
    # open trace group when a batch is emitted (FIFO group-per-batch)
    flush_causes = None         # probe's flush-cause counter dict
    flight = None               # FlightRecorder (observability wiring)
    flight_site = ""
    query_name = ""             # the profiler spans' <query> (bridge sets it)
    parts = None                # what collect and deliver filed for the
    # batch in hand (``measure``), taken by step_phases
    _sealed = None              # (thread, wall, CPU) at the last seal
    _pending_cause = None       # cause of the flush whose emit comes next
    driver = None               # AsyncDeviceDriver when the bridge pipelines
    callback = None             # deliver()'s fn(chunk, emit_ts)
    fence_key = "valid"         # the step output _decode reads first

    def add_callback(self, fn) -> None:
        """``fn(rows)`` per batch, for a runtime used by itself (a bridge
        sets ``callback`` to its own ``fn(chunk, emit_ts)``)."""
        self.callback = lambda out, emit_ts=None: fn(out.rows())

    # -- the flush rule ---------------------------------------------------------
    def _count_flush(self, cause: str) -> None:
        fc = self.flush_causes
        if fc is not None:
            fc[cause] = fc.get(cause, 0) + 1
        # the emitted batch inherits this cause (phase attribution keys the
        # deadline-queueing share off it)
        self._pending_cause = cause
        f = self.flight
        if f is not None:
            # transition-recorded: only a CHANGE of flush cause lands on the
            # flight timeline (capacity→deadline is the story; ten thousand
            # capacity flushes are not)
            f.record_transition("flow", f"flush:{cause}",
                                site=self.flight_site)

    def _take_cause(self):
        c = self._pending_cause
        self._pending_cause = None
        return c

    def _maybe_flush(self) -> None:
        """Flush on the hard capacity OR the adaptive soft threshold (jitted
        shapes stay static at capacity; only the fill level changes)."""
        c = self.batch_controller
        if self.builder.full:
            self._count_flush("capacity")
            self.flush()
        elif c is not None and len(self.builder) >= c.current:
            self._count_flush("adaptive")
            self.flush()

    # -- seal -------------------------------------------------------------------
    def _seal(self) -> None:
        """Close the probe's open trace group — call immediately before
        ``builder.emit()`` (every flush implementation does), so trace
        groups pair 1:1 with emitted batches."""
        s = self.step_sealer
        if s is not None:
            s()

    def _sealing(self, batch: dict) -> None:
        """A runtime's own work on a batch just emitted, inside the
        ``seal.pack`` span on the thread that seals it (a keyed window
        looks its keys up here); nothing by default."""

    def _emit_batch(self) -> dict:
        """Seal and emit the staged batch (every flush's first half): the
        probe's trace group closes exactly at the emit, and the flush cause
        rides the batch (phase attribution keys the deadline-queueing share
        off it). Where the thread that seals this batch sealed the one
        before it, the batch also carries that thread's wall and CPU seconds
        since then (``client_cycle`` / ``client_cpu``): everything the
        client's thread did for one batch; a seal by another thread (a
        deadline flush, a ``flush_sync``) breaks the chain for two batches."""
        here = (threading.get_ident(), time.perf_counter(),
                time.thread_time())
        last, self._sealed = self._sealed, here
        self._seal()
        with span(span_name("pack", self.query_name)):
            batch = self.builder.emit()
            self._sealing(batch)
        batch["_cause"] = self._take_cause()
        if last is not None and last[0] == here[0]:
            batch["_client_cycle_s"] = here[1] - last[1]
            batch["_client_cpu_s"] = here[2] - last[2]
        return batch

    # -- dispatch → fence → decode ---------------------------------------------
    def dispatch(self, batch: dict):
        """Fire-and-forget device step: advances ``self.state`` through
        donated buffers and returns the un-fenced output pytree, the token
        ``collect`` fences at the egress edge. A runtime whose ``collect``
        of this token will read live state back marks the batch
        ``_serial``: the driver dispatches nothing behind it until it is
        collected (a hopping window's drain, ``tpu/runtime.py``)."""
        raise NotImplementedError

    def _decode(self, out):
        """One fenced step's outputs as one ``ColumnsOut``."""
        raise NotImplementedError

    def _fence(self, first) -> None:
        """``collect``'s first half, told apart from the decode: wait until
        the step's outputs are ready on the device, by fetching ``first``,
        the output the decode reads first (the array keeps its host copy, so
        the decode's own ``np.asarray`` of it is free). This is the one
        synchronisation ``collect`` always had, in its place, plus that one
        copy. A ``block_until_ready`` of the outputs ahead of it is the purer
        fence and costs a paced query a millisecond of detection latency:
        the first copy then no longer queues behind the step on the device
        but waits for the host to wake and ask (PERF.md, PR 25)."""
        with self.measure("egress_fence"):
            np.asarray(first)

    def collect(self, out):
        """Egress fence + decode for one dispatched step: one ``ColumnsOut``
        chunk (falsy when empty), its string codes already resolved so that
        ``deliver`` holds the engine lock for the junction alone."""
        self._fence(out[self.fence_key])
        with span(span_name("egress_decode", self.query_name)):
            chunk = self._decode(out)
            chunk.decoded()
            return chunk

    def process(self, batch: dict):
        """Synchronous step + decode: one dispatch immediately collected
        (async: the driver's thread, no engine lock — device state is
        worker-owned)."""
        return self.collect(self.dispatch(batch))

    # -- deliver ----------------------------------------------------------------
    def deliver(self, out, emit_ts=None) -> None:
        fn = self.callback
        if fn is not None and out:
            fn(out, emit_ts)

    def flush(self):
        """Seal what is staged and step it: handed to the driver when there
        is one, else stepped here, delivered with the batch's own last event
        time and followed by the drain-point bookkeeping. Returns the sync
        path's chunk (None when nothing was staged or a driver took it)."""
        if len(self.builder) == 0:
            return None
        batch = self._emit_batch()
        if self.driver is not None:
            self.driver.submit(batch)
            return None
        out = self._timed_process(batch)
        self.deliver(out, batch.get("last_ts"))
        self.on_drained()
        return out

    def on_drained(self) -> None:
        """Called when the pipeline empties (the driver: also every 64th
        batch under load; the sync path: after every flush) — the safe point
        for bookkeeping that reads device state back."""

    def finalize(self) -> None:
        """Terminal flush at shutdown, for a kernel that holds an open
        segment."""

    # -- the phases record --------------------------------------------------------
    def observe_step(self, n_events: int, latency_s: float,
                     device_path: bool = True,
                     phases: Optional[dict] = None) -> None:
        """Feed one stepped batch's latency to the adaptive controller and
        the observability step probe (the async driver reports its own step
        timing through this hook). ``device_path=False`` marks a step whose
        work the resilience layer rerouted to the host interpreter — the
        controller must not tune on it, but the probe still drains its
        trace group. ``phases`` carries the batch's measured waterfall
        segments (X-Ray phase attribution)."""
        c = self.batch_controller
        if c is not None and device_path:
            c.observe(n_events, latency_s)
        obs = self.step_observer
        if obs is not None:
            obs(n_events, latency_s, device_path, phases=phases)

    def measure(self, name: str, batch: Optional[dict] = None,
                spanned: bool = True) -> _Measured:
        """``with self.measure(name):`` round a segment of
        ``phases.SEGMENTS``: its span (where ``spanned``), the wall clock
        and, where the table names a companion, the thread's CPU clock. The
        seconds are filed on ``batch`` where the batch is in hand (seal,
        dispatch), else in ``parts`` (collect, deliver)."""
        return _Measured(self._parts_of(batch), name,
                         span_name(name, self.query_name) if spanned else None)

    def _parts_of(self, batch: Optional[dict] = None) -> dict:
        if batch is not None:
            return batch.setdefault("_parts", {})
        if self.parts is None:
            self.parts = {}
        return self.parts

    def file_part(self, name: str, seconds: float) -> None:
        """Add a reading taken elsewhere (a subscriber's own build) to the
        batch in hand's ``name``."""
        parts = self._parts_of()
        parts[name] = parts.get(name, 0.0) + seconds

    def step_phases(self, batch: dict, queue_s: float, step_s: float,
                    step_cpu_s: float, collect_s: float,
                    collect_cpu_s: float, **driver_s) -> dict:
        """One device batch's waterfall as ``PhaseBreakdown.record_batch``
        names it: what the batch carries (fill span, pack, cause, the
        client's cycle), what whoever stepped it measured on the wall clock
        and, beside it, on its thread's CPU clock, in ``driver_s`` what only
        the async driver has (``ring_s``, ``lock_s``, ``publish_s``,
        ``publish_cpu_s``, ``driver_cpu_s``), and the batch's ``parts``:
        what ``measure`` filed on it and in the runtime's ``parts``, taken
        here. ``collect`` is cut into the fence filed there and the decode;
        a collect that never fenced is all wait."""
        parts = {**batch.get("_parts", {}), **(self.parts or {})}
        self.parts = None
        fence_s = parts.pop("egress_fence", None)
        fence_cpu_s = parts.pop(SEGMENTS["egress_fence"].cpu, None)
        if fence_s is None:
            fence_s, fence_cpu_s = collect_s, collect_cpu_s
        return {
            "fill_span_s": batch.get("pack_s", 0.0),
            "pack_s": batch.get("pack_exec_s", 0.0),
            "queue_s": queue_s,
            "step_s": step_s,
            "step_cpu_s": step_cpu_s,
            "fence_s": fence_s,
            "fence_cpu_s": fence_cpu_s,
            "decode_s": collect_s - fence_s,
            "decode_cpu_s": collect_cpu_s - fence_cpu_s,
            "client_cycle_s": batch.get("_client_cycle_s"),
            "client_cpu_s": batch.get("_client_cpu_s"),
            "cause": batch.get("_cause"),
            "parts": parts,
            **driver_s,
        }

    def _timed_process(self, batch: dict):
        """Sync-path step, timed for the controller/probe with the
        dispatch/fence/decode split measured separately (the ``device_step``
        / ``egress_fence`` / ``egress_decode`` phases, each with the
        client thread's CPU beside it; on the sync path there is no ring,
        so ``ingress_queue`` is the emit→dispatch gap alone)."""
        if self.batch_controller is None and self.step_observer is None:
            return self.process(batch)
        q = self.query_name
        self.parts = None
        t0, c0 = time.perf_counter(), time.thread_time()
        try:
            with span(span_name("device_step", q)):
                token = self.dispatch(batch)
            t1, c1 = time.perf_counter(), time.thread_time()
            with span(f"siddhi:collect:{q}"):
                rows = self.collect(token)
        except BaseException:
            # a raising step still consumed its batch: the probe must pop
            # this batch's trace group or every later device span would be
            # attributed one batch off, forever
            self.observe_step(batch.get("count", 0),
                              time.perf_counter() - t0, device_path=False)
            raise
        t2, c2 = time.perf_counter(), time.thread_time()
        t_emit = batch.get("_t_emit")
        phases = self.step_phases(
            batch,
            queue_s=max(0.0, t0 - t_emit) if t_emit is not None else 0.0,
            step_s=t1 - t0, step_cpu_s=c1 - c0,
            collect_s=t2 - t1, collect_cpu_s=c2 - c1)
        self.observe_step(batch.get("count", 0), t2 - t0, phases=phases)
        return rows
