"""Detection-latency attribution: per-query per-phase histograms.

The r3 device round reported p99 detection latency of 2.9s "dominated by
deadline-flush queueing" — but nothing in the engine could *prove* that
decomposition. This module is the evidence substrate (TiLT's per-operator
time attribution, arXiv:2301.12030; Hazelcast Jet's queueing-vs-processing
split, arXiv:2103.10169): every micro-batch's journey is cut into serial
waterfall segments, each recorded event-weighted into an always-on
:class:`~siddhi_tpu.observability.histogram.LogHistogram`, so phase means
SUM to the end-to-end mean by construction and the per-phase p99s say
where a tail came from.

``SEGMENTS`` names every segment of a served batch once: its ``phase.*``
tracker, the phase it lies inside (a part, outside the serial sum; none for
a serial phase), its ``siddhi:`` span on the profiler's clock
(``profiler.py``), the tracker of its thread's CPU clock, and when it is
recorded. ``PHASES``, ``NESTED``, ``CPU_OF`` and ``THREAD_CLOCKS`` are views
of it. A served runtime records a part with ``StepRuntime.measure``
(``tpu/step_runtime.py``), which opens the span, reads the clocks and files
the reading; ``record_batch`` takes a batch's parts as one dict.

The CPU clock is ``time.thread_time``: it advances while the calling thread
runs, in Python or in C with the GIL let go, and stands still while it
waits for the GIL, a lock, a condition, the device or the scheduler (XLA's
own threads are not counted). A companion is recorded for every batch its
wall tracker is recorded for, zero included, so wall less CPU is what the
thread waited. ``lock_wait`` and ``ring_wait`` are waits by definition and
have none. Where the kernel keeps thread CPU time by ticks (the TPU v5e
hosts this repo is measured on: steps of 10 ms, one read a system call of
about 6 us), one batch's reading is 0 or a tick and only the MEAN over
hundreds of batches reads true: ``/latency`` gives ``cpu_ms`` as a mean and
no percentile of it.
"""

from __future__ import annotations

from typing import NamedTuple, Optional


class Segment(NamedTuple):
    parent: Optional[str]   # the phase a part lies inside; None: serial
    span: Optional[str]     # ``siddhi:<span>:<query>``; None: no span
    cpu: Optional[str]      # tracker of its thread's CPU seconds, or None
    stage: Optional[str]    # the trace stage (``tracing.py``) it stands for
    when: str               # when it is recorded


_ = None    # an empty cell
SEGMENTS = {
    "ingress_parse": Segment(
        _, _, _, "parse", "a columnar source's bytes to columns, encoded"),
    "ingress_queue": Segment(
        _, _, _, "queue", "seal to dispatch to its collect, less ring_wait"),
    "ring_wait": Segment(
        _, "submit.ring_wait", _, _, "the client's submit to a full ring"),
    "fill_wait": Segment(
        _, _, _, "fill-wait", "a batch filling: span / 2, not measured"),
    "pack": Segment(
        _, "seal.pack", _, "pack", "every seal: emit and _sealing, lock held"),
    "device_step": Segment(
        _, "dispatch", "device_step_cpu", "device", "rt.dispatch: copies "
        "in and the launch, an enqueue"),
    "egress_fence": Segment(
        _, "collect.fence", "egress_fence_cpu", "fence", "collect until "
        "the first output the decode reads is on the host"),
    "egress_decode": Segment(
        _, "collect.decode", "egress_decode_cpu", _, "the rest of collect: "
        "copies out, one ColumnsOut"),
    "host_exec": Segment(
        _, _, _, _, "host tiers: interpreter, columnar, fleet, replays"),
    "lock_wait": Segment(
        _, "deliver.lock", _, _, "the driver asking for the engine lock"),
    "sink_publish": Segment(
        _, "deliver.publish", "sink_publish_cpu", "sink", "rt.deliver of "
        "a chunk with rows, engine lock held"),
    "dcn_transit": Segment(
        _, _, _, "dcn", "the cross-host hop, send to apply"),
    "procmesh_transit": Segment(
        _, _, _, "procmesh", "parent to child process, dispatch to apply"),
    "route": Segment(
        "device_step", "dispatch.route", "route_cpu", _, "every batch of a "
        "served partition: its lanes laid out"),
    "key_lookup": Segment(
        "pack", "seal.key_lookup", "key_lookup_cpu", _, "every batch of a "
        "keyed window: its keys to slots, as it is sealed"),
    "decode_full": Segment(
        "egress_decode", "collect.decode.full", _, _, "a batch with more "
        "rows than its packed table: the decode of `full`"),
    "hop_drain": Segment(
        "egress_decode", "collect.decode.hop_drain", _, _, "every batch of "
        "a hopping window; the span only round a drain"),
    "hop_flush": Segment(
        "egress_decode", "collect.decode.hop_flush", _, _, "a hopping "
        "window's batch whose step fired a boundary with rows"),
    "publish_build": Segment(
        "sink_publish", "deliver.publish.build", _, _, "every delivery: "
        "the Events built for a subscriber, 0 for columns"),
}

PHASES = tuple(p for p, s in SEGMENTS.items() if s.parent is None)
NESTED = {p: s.parent for p, s in SEGMENTS.items() if s.parent is not None}
CPU_OF = {p: s.cpu for p, s in SEGMENTS.items() if s.cpu is not None}
# beside the companions, the threads whole: ``client_cycle`` the wall from
# one seal to the next by the same thread (all it did for a batch, its own
# loop included), ``client_cpu`` that thread's CPU over it, ``driver_cpu``
# the driver thread's CPU from one batch's last reading to the next's
THREAD_CLOCKS = tuple(CPU_OF.values()) + ("client_cycle", "client_cpu",
                                          "driver_cpu")
# trace stage -> phase; unknown stages (``ingress``, ``query``, ``fleet``:
# every host-side processor span nests inside the query chain) are host work
_STAGE_PHASE = {s.stage: p for p, s in SEGMENTS.items() if s.stage}


def span_name(segment: str, label: str) -> str:
    """The segment's span, for the query (or stream) ``label``."""
    return f"siddhi:{SEGMENTS[segment].span}:{label}"


def phase_of_stage(stage: str) -> str:
    return _STAGE_PHASE.get(stage, "host_exec")


class PhaseBreakdown:
    """One query's per-phase latency attribution.

    ``record_batch`` takes the measured serial segments of one stepped
    micro-batch (seconds) and records each event-weighted; the end-to-end
    sample is the SUM of the segments, so
    ``sum(phase means) == end_to_end mean`` exactly and any drift in a
    report indicates a measurement bug, not an accounting choice.
    ``fill_span_s`` is the full first-append→seal window; its per-event
    average (span/2) is what both fill_wait and end_to_end see.
    """

    def __init__(self, make_tracker):
        """``make_tracker(name)`` → a LatencyTracker-like with
        ``record_seconds(seconds, n=1, exemplar=None)``."""
        self.trackers = {p: make_tracker(p) for p in
                         tuple(SEGMENTS) + THREAD_CLOCKS}
        self.end_to_end = make_tracker("end_to_end")
        # queueing attributable to flush policy, split by flush cause —
        # the "deadline-flush queueing share" field reads from these
        self.wait_sum_by_cause: dict[str, float] = {}
        self.e2e_sum = 0.0
        self.batches = 0

    def record_batch(self, n: int, fill_span_s: float = 0.0,
                     pack_s: float = 0.0, queue_s: float = 0.0,
                     step_s: float = 0.0, fence_s: float = 0.0,
                     publish_s: float = 0.0, host_s: float = 0.0,
                     parse_s: float = 0.0, ring_s: float = 0.0,
                     decode_s: float = 0.0, lock_s: float = 0.0,
                     step_cpu_s: Optional[float] = None,
                     fence_cpu_s: Optional[float] = None,
                     decode_cpu_s: Optional[float] = None,
                     publish_cpu_s: Optional[float] = None,
                     client_cycle_s: Optional[float] = None,
                     client_cpu_s: Optional[float] = None,
                     driver_cpu_s: Optional[float] = None,
                     parts: Optional[dict] = None,
                     cause: Optional[str] = None,
                     exemplar=None) -> None:
        """The ``*_cpu_s`` are the recording thread's CPU seconds
        (``time.thread_time``) over the stretch of the wall value beside
        them; None where nobody read that clock (the host tiers).
        ``parts`` holds the batch's parts as filed (``NESTED`` and their
        companions: tracker -> seconds), each recorded as it is, outside
        the serial sum."""
        if n <= 0:
            return
        fill_avg = max(0.0, fill_span_s) / 2.0
        # (tracker, wall seconds, its thread's CPU seconds or None): the
        # serial sum
        segs = (("ingress_parse", parse_s, None),
                ("fill_wait", fill_avg, None), ("pack", pack_s, None),
                ("ring_wait", ring_s, None), ("ingress_queue", queue_s, None),
                ("device_step", step_s, step_cpu_s),
                ("egress_fence", fence_s, fence_cpu_s),
                ("egress_decode", decode_s, decode_cpu_s),
                ("lock_wait", lock_s, None),
                ("sink_publish", publish_s, publish_cpu_s),
                ("host_exec", host_s, None))
        total = 0.0
        for phase, v, cpu in segs:
            if v > 0.0:
                self.trackers[phase].record_seconds(v, n, exemplar=exemplar)
                total += v
                # a companion wherever its wall tracker is, zero included:
                # the two counts stay equal
                if cpu is not None:
                    self.trackers[CPU_OF[phase]].record_seconds(cpu, n)
        for name, v in (parts or {}).items():
            self.trackers[name].record_seconds(
                v, n, exemplar=exemplar if name in NESTED else None)
        # the two threads whole (THREAD_CLOCKS): a client's cycle only
        # where one thread sealed this batch and the one before it
        for name, v in (("client_cycle", client_cycle_s),
                        ("client_cpu", client_cpu_s),
                        ("driver_cpu", driver_cpu_s)):
            if v is not None:
                self.trackers[name].record_seconds(v, n)
        self.end_to_end.record_seconds(total, n, exemplar=exemplar)
        self.e2e_sum += total * n
        self.batches += 1
        if cause is not None:
            self.wait_sum_by_cause[cause] = \
                self.wait_sum_by_cause.get(cause, 0.0) + fill_avg * n

    # -- readouts --------------------------------------------------------------
    def queueing_share(self, cause: str = "deadline") -> float:
        """Fraction of total end-to-end latency spent as fill-wait on
        batches flushed for ``cause`` — the field that proves (or refutes)
        "p99 dominated by deadline-flush queueing"."""
        if self.e2e_sum <= 0.0:
            return 0.0
        return self.wait_sum_by_cause.get(cause, 0.0) / self.e2e_sum

    def _off_cpu_share(self, cpu: str, wall: str) -> float:
        """The share of ``wall``'s seconds its thread did not run."""
        w = self.trackers[wall].hist.sum
        return round(max(0.0, 1.0 - self.trackers[cpu].hist.sum / w), 6) \
            if w > 0.0 else 0.0

    def report(self) -> dict:
        e2e = self.end_to_end.percentiles_ms()
        phases = {p: t.percentiles_ms() for p, t in self.trackers.items()
                  if t.count and p not in THREAD_CLOCKS}
        for wall, cpu in CPU_OF.items():
            t = self.trackers[cpu]
            if wall in phases and t.count:
                phases[wall]["cpu_ms"] = round(
                    t.hist.sum / t.count * 1e3, 6)
                phases[wall]["off_cpu_share"] = self._off_cpu_share(cpu, wall)
        # reconciliation from SUMS over the e2e event count, not from the
        # per-phase means: a segment absent on some batches (sink_publish
        # records only when a batch produced rows) has a conditional mean,
        # and summing conditional means would overstate the decomposition.
        # Σ(phase sums) == Σ(e2e samples) by construction, so this ratio is
        # exactly 1.0 unless a measurement bug slips in.
        total_events = self.end_to_end.count
        mean_sum = (sum(self.trackers[p].hist.sum for p in PHASES)
                    / total_events * 1e3) if total_events else 0.0
        out = {
            "end_to_end": e2e,
            "phases": phases,
            "phase_mean_sum_ms": round(mean_sum, 6),
            "end_to_end_mean_ms": round(e2e["avg_ms"], 6),
            "deadline_flush_queueing_share":
                round(self.queueing_share("deadline"), 6),
            "queueing_share_by_cause": {
                c: (round(s / self.e2e_sum, 6) if self.e2e_sum else 0.0)
                for c, s in self.wait_sum_by_cause.items()},
        }
        # the two threads whole: a tracker's sum over its count is seconds
        # a batch (event-weighted), over the mean batch seconds an event
        client = self.trackers["client_cpu"]
        driver = self.trackers["driver_cpu"]
        if client.count:
            out["client_cpu_us_per_event"] = round(
                client.hist.sum / client.count
                / (total_events / self.batches) * 1e6, 6)
            out["client_off_cpu_share"] = self._off_cpu_share(
                "client_cpu", "client_cycle")
        if driver.count:
            out["driver_cpu_ms_per_batch"] = round(
                driver.hist.sum / driver.count * 1e3, 6)
        if e2e["avg_ms"] > 0.0:
            out["reconciliation_ratio"] = round(mean_sum / e2e["avg_ms"], 6)
        return out
