"""Device-path quarantine: a circuit breaker over the TPU execution path.

A compile-time failure already falls back to the host interpreter
(``DeviceCompileError`` in ``core/device_bridge.py``); this module covers the
*runtime* gap: a device step that crashes mid-stream used to log and drop its
whole micro-batch. The guard wraps every bridge runtime's ``process``:

- each submitted batch carries a host-side **shadow** of its raw rows
  (``_ShadowBuilder`` wraps the bridge's batch builder);
- a failing step records a breaker failure and replays the shadow through a
  lazily-built host interpreter runtime for the same query (the reference's
  CPU ``QueryRuntime`` role; for a served ``partition with`` block, kind
  ``'partition'``, the per-key ``PartitionRuntime`` of the same block), so
  no event is lost;
- after ``device.circuit.threshold`` consecutive failures the device path is
  **quarantined** — steps short-circuit straight to the host fallback without
  touching the device — and after ``device.circuit.cooldown.ms`` the next
  batch runs as a half-open probe that re-promotes the device path on
  success.

Parity caveat (documented in DISTRIBUTED.md): the host fallback runtime owns
its own state, so fallback output is exact for stateless queries (filters,
projections); for windowed/pattern/join queries the fallback preserves the
events but its state starts from the quarantine point.
"""

from __future__ import annotations

import logging
import threading
from typing import Callable, Optional

from .chaos import ChaosInjector
from .circuit import CircuitBreaker, CircuitState

log = logging.getLogger("siddhi_tpu.resilience")


class _ShadowCols:
    """Lazy shadow of one columnar chunk slice: the raw column references
    (numpy slices are views — cheap) materialize to replayable rows ONLY
    when a fault actually consumes the shadow (the FleetGuard
    ``admit_columns`` discipline — the zero-object path must not pay a
    per-row Python tax for a replay that almost never happens)."""

    __slots__ = ("cols", "ts", "names")

    def __init__(self, cols: dict, ts, names: list):
        self.cols = cols
        self.ts = ts
        self.names = names

    def rows(self) -> list:
        from ..core.columns import columns_to_rows
        n = int(self.ts.shape[0])
        return [(None, row, int(t)) for row, t in zip(
            columns_to_rows(self.cols, self.names, n), self.ts.tolist())]


class _ShadowBuilder:
    """Batch-builder proxy retaining the raw rows of the batch being packed,
    so a failed device step can replay exactly those events on the host.

    Wraps both builder shapes: ``BatchBuilder.append(row, ts)`` (single
    stream) and ``MergedBatchBuilder.append(stream_id, row, ts)``, plus the
    columnar chunk path (``append_columns`` — shadowed as lazy column
    slices, materialized only on fault). The bulk pre-encoded path
    (``append_many``) has no row-level shadow — batches that used it are
    marked incomplete and a failed step can only count, not replay, them."""

    def __init__(self, inner, merged: bool):
        self._inner = inner
        self._merged = merged
        self._rows: list = []           # (stream_id | None, row, ts)
        self._incomplete = False

    def __len__(self):
        return len(self._inner)

    @property
    def full(self):
        return self._inner.full

    def append(self, *args) -> None:
        self._inner.append(*args)       # may raise OverflowError — first
        if self._merged:
            sid, row, ts = args
        else:
            (row, ts), sid = args, None
        self._rows.append((sid, list(row), ts))

    def append_rows(self, rows, ts_list) -> None:
        if self._merged:
            # MergedBatchBuilder has no bulk row API; mirroring one here
            # would desynchronize the shadow
            raise TypeError("append_rows is single-stream only")
        for row, ts in zip(rows, ts_list):
            self.append(row, ts)

    def append_sentinel(self, row, ts) -> None:
        """Device-only bookkeeping row (e.g. the timeBatch finalize
        sentinel): packed into the batch but excluded from the host-fallback
        shadow — it is not an event and must never replay."""
        self._inner.append(row, ts)
        self._rows.append(None)

    def append_columns(self, cols: dict, ts, start: int = 0) -> int:
        """Columnar chunk staging WITH a (lazy) shadow: the inner builder
        takes what fits, the shadow keeps references to exactly that slice.
        Without this override ``__getattr__`` would route straight to the
        inner builder and silently leave the shadow missing rows — a failed
        step would then replay a PARTIAL batch."""
        import numpy as np
        ts = np.asarray(ts, dtype=np.int64)
        take = self._inner.append_columns(cols, ts, start)
        if take:
            sl = slice(start, start + take)
            names = self._inner.column_names
            self._rows.append(_ShadowCols(
                {n: cols[n][sl] for n in names}, ts[sl], names))
        return take

    def append_many(self, *args, **kwargs):
        self._incomplete = True
        return self._inner.append_many(*args, **kwargs)

    def emit(self) -> dict:
        batch = self._inner.emit()
        batch["_shadow_rows"] = None if self._incomplete else self._rows
        self._rows = []
        self._incomplete = False
        return batch

    def snapshot(self):
        return self._inner.snapshot()

    def restore(self, snap) -> None:
        self._inner.restore(snap)
        # restored staged rows have no shadow — don't mismatch rows to events
        self._rows = []
        self._incomplete = len(self._inner) > 0

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _PartitionFallback:
    """The host engine a served partition's failed batch replays through:
    the per-key interpreter ``PartitionRuntime`` of the same ``partition
    with`` block, shaped like a query runtime (``subscriptions``,
    ``start``) so that the guard feeds it as it feeds one."""

    def __init__(self, partition_ast, app_context, stream_defs: dict,
                 get_junction, name: str, query_callbacks: list):
        from ..core.partition import PartitionRuntime, PartitionStreamReceiver
        self.prt = PartitionRuntime(partition_ast, app_context, stream_defs,
                                    get_junction, name)
        # callbacks registered on the device query see replayed rows too
        for q in partition_ast.queries:
            if q.name() is not None:
                self.prt.query_callbacks[q.name()] = query_callbacks
        self.subscriptions = [
            (sid, PartitionStreamReceiver(self.prt, sid,
                                          self.prt.key_executors.get(sid)))
            for sid in sorted(self.prt.consumed)]

    def start(self) -> None:
        """Key instances start as their first event arrives."""


class _GuardToken:
    """In-flight pipeline slot: the inner runtime's un-fenced output token
    plus everything needed to replay the batch on the host if the step turns
    out to have failed. Tokens travel the async driver's FIFO ring, so a
    failed batch's host replay runs at its own egress slot — after every
    earlier batch delivered, before every later one — which is what makes a
    mid-pipeline fault unable to reorder or double-emit a micro-batch."""

    __slots__ = ("inner", "shadow", "batch", "failed", "quarantined")

    def __init__(self, inner, shadow, batch, failed=False, quarantined=False):
        self.inner = inner
        self.shadow = shadow
        self.batch = batch
        self.failed = failed
        self.quarantined = quarantined


class DeviceGuard:
    """Wraps one device bridge runtime with failure capture + quarantine.

    The wrap is two-phase, matching the pipelined runtime API: ``dispatch``
    captures the batch's host shadow and fires the inner step (fire-and-
    forget — an asynchronously dispatched step's failure may only surface at
    the fence), ``collect`` fences and, on failure, replays the shadow
    through the host fallback at the token's own FIFO egress slot. The
    synchronous path (``rt.process``) goes through the same two wrapped
    phases back-to-back."""

    def __init__(self, query, query_name: str, app_context, stream_defs: dict,
                 get_junction: Callable, kind: str,
                 failure_threshold: int = 3, cooldown_s: float = 30.0,
                 chaos: Optional[ChaosInjector] = None):
        self.query = query
        self.query_name = query_name
        self.app_context = app_context
        self.stream_defs = dict(stream_defs)
        self.get_junction = get_junction
        self.kind = kind
        self.breaker = CircuitBreaker(failure_threshold, cooldown_s)
        self.chaos = chaos
        self._site = f"device:{app_context.name}/{query_name}"
        self.failures = 0
        self.fallback_events = 0        # events replayed through the host
        self.lost_events = 0            # shadow-less batches (bulk ingress)
        self.bridge = None              # set by guard_device for callbacks
        self.flight = None              # FlightRecorder (observability wiring)
        self._last_step_fell_back = False
        self._fb_runtime = None
        self._fb_engine = None          # 'columnar' | 'scalar' once built
        self._fb_lock = threading.Lock()

    # -- installation --------------------------------------------------------
    def install(self, rt) -> None:
        """Wrap ``rt.dispatch``/``rt.collect`` and ``rt.builder`` in place
        (instance attributes shadow the methods). Both execution paths go
        through the wrapped pair: the async driver calls dispatch/collect
        directly; the sync path's ``rt.process`` is defined as
        ``collect(dispatch(batch))`` and resolves the instance attributes."""
        rt.builder = _ShadowBuilder(rt.builder, merged=self.kind != "stream")
        inner_dispatch = rt.dispatch
        inner_collect = rt.collect
        rt.dispatch = lambda batch: self.dispatch(inner_dispatch, batch)
        rt.collect = lambda token: self.collect(inner_collect, token)
        # failed/quarantined steps time the HOST replay, not the device —
        # feeding those samples to the adaptive batch controller would tune
        # it on latencies unrelated to device performance. The observability
        # probe must still see the step (device_path=False) or its pending
        # trace groups would pile up for the whole quarantine.
        inner_observe = getattr(rt, "observe_step", None)
        if inner_observe is not None:
            def observe(n_events, latency_s, device_path=True, phases=None):
                inner_observe(
                    n_events, latency_s,
                    device_path=device_path and not self._last_step_fell_back,
                    phases=phases)
            rt.observe_step = observe

    # -- two-phase step ------------------------------------------------------
    def dispatch(self, inner_dispatch, batch: dict) -> _GuardToken:
        """Fire the inner step; failures (chaos injection, jit trace errors,
        an open circuit) do NOT raise — they ride the returned token to its
        FIFO egress slot, where the host replay happens in order."""
        shadow = batch.pop("_shadow_rows", None)
        if not self.breaker.allow():
            return _GuardToken(None, shadow, batch,
                               failed=True, quarantined=True)
        try:
            if self.chaos is not None:
                self.chaos.on_device(self._site)
            inner = inner_dispatch(batch)
        except Exception as e:  # noqa: BLE001 — quarantine boundary: the
            # failed batch reroutes to the host path, the app keeps running
            self._record_failure(e)
            return _GuardToken(None, shadow, batch, failed=True)
        return _GuardToken(inner, shadow, batch)

    def collect(self, inner_collect, token: _GuardToken) -> list:
        """Egress edge: fence the inner token (an async-dispatched step's
        failure surfaces HERE, not at dispatch) and replay the shadow on
        failure. Called strictly FIFO by the driver — earlier batches have
        already delivered, so replay cannot reorder."""
        if token.failed:
            self._last_step_fell_back = True
            self._host_fallback(token.shadow, token.batch,
                                quarantined=token.quarantined)
            return []
        try:
            rows = inner_collect(token.inner)
        except Exception as e:  # noqa: BLE001 — same quarantine boundary,
            # one pipeline stage later
            self._record_failure(e)
            self._last_step_fell_back = True
            self._host_fallback(token.shadow, token.batch)
            return []
        self.breaker.record_success()
        self._last_step_fell_back = False
        return rows

    def _record_failure(self, e: Exception) -> None:
        self.failures += 1
        was_open = self.breaker.state == CircuitState.OPEN
        self.breaker.record_failure()
        log.warning("%s: device step failed (%d consecutive, circuit %s)"
                    ": %s", self._site,
                    self.breaker.consecutive_failures,
                    self.breaker.state, e, exc_info=True)
        fl = self.flight
        if fl is not None:
            fl.record("device", "step_failed", site=self.query_name,
                      detail={"error": f"{type(e).__name__}: {e}"[:200]})
            if not was_open and self.breaker.state == CircuitState.OPEN:
                # quarantine engaged: dump the control-plane timeline so the
                # post-mortem ships with the fault
                fl.record("device", "quarantined", site=self.query_name)
                fl.on_fault("device_quarantine", site=self.query_name)

    # -- host fallback -------------------------------------------------------
    def _fallback_runtime(self):
        # root_lock FIRST (consistent with the sync delivery path, where it
        # is already held): building registers state holders in
        # app_context.state_registry, which the snapshot walk iterates under
        # the same lock — an unlocked build from the async worker would race
        # it. _fb_lock then serializes the build itself.
        with self.app_context.root_lock:
            with self._fb_lock:
                if self._fb_runtime is None and self.kind == "partition":
                    # `query` is the whole Partition element: its keys'
                    # state lives in per-key interpreter instances
                    self._fb_runtime = _PartitionFallback(
                        self.query, self.app_context, self.stream_defs,
                        self.get_junction, f"{self.query_name}__hostfb",
                        self.bridge.query_callbacks
                        if self.bridge is not None else [])
                    self._fb_engine = "scalar"
                if self._fb_runtime is None:
                    # COLUMNAR first: quarantine/shadow-replay through the
                    # vectorized host engine (tpu/host_exec.py) — degraded
                    # mode runs at micro-batch speed, not one event at a
                    # time. Queries that don't lower on the numpy backend
                    # keep the scalar interpreter runtime.
                    fb = None
                    try:
                        from ..core.host_bridge import build_host_fallback
                        fb = build_host_fallback(
                            self.query, self.app_context, self.stream_defs,
                            self.get_junction, f"{self.query_name}__hostfb")
                    except Exception:   # noqa: BLE001 — fallback of the
                        # fallback: never let the fast path's absence turn
                        # a degraded device into a dead query
                        log.exception(
                            "%s: columnar fallback build failed; using the "
                            "scalar interpreter", self._site)
                    if fb is not None:
                        if self.bridge is not None:
                            # SHARE the bridge's query-callback list (see
                            # the scalar branch below)
                            fb.bridge.query_callbacks = \
                                self.bridge.query_callbacks
                        self._fb_runtime = fb
                        self._fb_engine = "columnar"
                        self._fb_runtime.start()
                        return self._fb_runtime
                    from ..core.query_runtime import build_query_runtime
                    self._fb_runtime = build_query_runtime(
                        self.query, self.app_context, self.stream_defs,
                        self.get_junction, f"{self.query_name}__hostfb")
                    self._fb_engine = "scalar"
                    if self.bridge is not None:
                        # SHARE the bridge's query-callback list: callbacks
                        # registered on the device query (now or later) see
                        # fallback outputs too, not just on-device ones
                        self._fb_runtime.callback_adapter.callbacks = \
                            self.bridge.query_callbacks
                    self._fb_runtime.start()
                return self._fb_runtime

    def _host_fallback(self, shadow, batch: dict,
                       quarantined: bool = False) -> None:
        if shadow is None:
            n = int(batch.get("count", 0))
            self.lost_events += n
            log.error("%s: no host shadow for a failed batch of %d events "
                      "(bulk-ingress batches cannot be replayed)",
                      self._site, n)
            return
        # None markers are append_sentinel() bookkeeping rows, not events;
        # _ShadowCols markers are lazy columnar slices — they materialize
        # to rows HERE, on the fault path only
        expanded: list = []
        for s in shadow:
            if s is None:
                continue
            if isinstance(s, _ShadowCols):
                expanded.extend(s.rows())
            else:
                expanded.append(s)
        shadow = expanded
        if not shadow:
            return
        rt = self._fallback_runtime()
        receivers = rt.subscriptions        # [(stream_id, receiver)]
        from ..core.event import EventType, StreamEvent
        delivered = 0
        with self.app_context.root_lock:
            for sid, row, ts in shadow:
                ev = StreamEvent(ts, list(row), EventType.CURRENT)
                for rsid, receiver in receivers:
                    if sid is None or rsid == sid:
                        receiver.receive(ev)
                delivered += 1
            if self._fb_engine == "columnar":
                # columnar receivers STAGE rows; one vectorized step per
                # replayed batch surfaces the outputs immediately
                rt.flush()
        self.fallback_events += delivered
        log.info("%s: %d event(s) rerouted through the host path%s",
                 self._site, delivered,
                 " (device quarantined)" if quarantined else "")

    # -- introspection -------------------------------------------------------
    def report(self) -> dict:
        return {
            "query": self.query_name,
            "circuit": self.breaker.state,
            "failures": self.failures,
            "fallback_events": self.fallback_events,
            "lost_events": self.lost_events,
            # which engine replays shadows: 'columnar' (vectorized host
            # fast path) or 'scalar'; None until the first fallback
            "fallback_engine": self._fb_engine,
        }
