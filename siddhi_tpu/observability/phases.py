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

Phases (one vocabulary for span classification, the ``phase.*`` latency
trackers, and the ``/latency`` report):

- ``ingress_parse`` — transport bytes → columns at the edge (CSV/SoA
  parse + dictionary encode in a columnar source, PR 11);
- ``ingress_queue`` — waiting in an @async junction buffer or the device
  driver's staged/in-flight ring: seal → dispatch, and dispatched → its
  turn to be collected (less ``ring_wait``);
- ``ring_wait``     — the client's wait in ``AsyncDeviceDriver.submit`` on
  a full ring (engine lock held; stamped on the batch, zero where the
  ring had room);
- ``fill_wait``     — waiting for a micro-batch window to fill (recorded
  as the per-event AVERAGE wait, span/2, under the uniform-arrival
  approximation — the only non-measured segment);
- ``pack``          — SoA staging/emit of the batch;
- ``device_step``   — the host's time inside ``rt.dispatch``: copies in
  and the launch of the jitted step. An ENQUEUE, not device time (JAX
  returns while the device computes; device time comes from a trace);
- ``egress_fence``  — the wait, inside ``rt.collect``, until the step's
  outputs are ready on the device, taken as the fetch of the first output
  the decode reads (the wait plus that one copy);
- ``egress_decode`` — the rest of ``rt.collect``: the other copies to the
  host and the columns masked into one ``ColumnsOut`` chunk
  (``decode_outputs``) with its string codes
  resolved; no row is built here;
- ``host_exec``     — host-tier execution (interpreter, columnar,
  fleet lanes, shadow replays);
- ``lock_wait``     — the driver thread asking for the engine lock (which
  a client holds inside every ``send``) until it is held;
- ``sink_publish``  — delivery/publish downstream of the step, lock held:
  the chunk to the junction (``core/egress.py``: columns as they are, or
  rows and events built in bulk), callbacks;
- ``dcn_transit``   — the cross-host hop (send wall-clock → apply);
- ``procmesh_transit`` — the parent→child control-socket hop in a
  process-per-host fabric (dispatch wall-clock → child apply, including
  any lost-ack retry delay).

Two parts of a phase are told apart without joining the serial sum
(``NESTED``): ``route`` — a served partition's lane layout of the flat
batch, inside ``device_step`` (``tpu/partition.py`` ``dispatch``; span
``siddhi:dispatch.route``); ``key_lookup`` — a served keyed window's way
from a key to its slot, inside ``pack``, on the thread that seals the batch
(the client's for a capacity flush): the directory's search and the keys it
admits (``tpu/keyed_window.py`` ``_sealing``; span
``siddhi:seal.key_lookup``, inside ``siddhi:seal.pack``); ``decode_full``
— an NFA's decode of its ``full`` table (the blocked kernel's whole
candidate table, the scan kernel's table of the plan's bound), inside
``egress_decode``, which runs only for a batch in which a lane emitted
more rows than the packed row table holds (``tpu/nfa.py``
``decode_rows``; span ``siddhi:collect.decode.full``): its count over
``egress_decode``'s says how often that was, and for the scan kernel how
often the step took its whole pack; ``hop_drain`` — a hopping window's
drain, inside ``egress_decode``, recorded for every batch:
the test of whether the batch was dispatched serial (its step may have left
a boundary deferred, decided on the host from its timestamps) and, for a
serial batch only, the read of ``hop_next`` / ``last_ts`` out of the live
state and any empty steps for deferred boundaries (``tpu/runtime.py``
``_decode``; span ``siddhi:collect.decode.hop_drain``, round a drain that
runs; the serial batches are the gauge ``hop_serial_batches``);
``hop_flush`` — the decode of a batch
whose step fired a boundary with rows (span
``siddhi:collect.decode.hop_flush``): its count over ``egress_decode``'s is
the share of batches that carried a boundary; ``publish_build`` — what the
ENGINE builds inside ``sink_publish`` for a subscriber that takes events:
timestamps, rows and a ``StreamEvent`` a row, the ``Event`` list of a query
callback (``core/egress.py``), or the ``Event`` list a ``StreamCallback``'s
receiver builds from a columnar chunk (``core/stream.py``
``receive_columns``); span ``siddhi:deliver.publish.build``; 0 where every
subscriber takes the columns as they are; the rest of ``sink_publish`` is
the junction and the subscribers' own functions.

Beside the wall clock, the threads' own CPU clocks (``CPU_OF``,
``THREAD_CLOCKS``;
``time.thread_time``, which advances while the calling thread runs, in
Python or in C with the GIL let go, and stands still while it waits for
the GIL, a lock, a condition, the device or the scheduler; XLA's own
threads are not counted). All are outside the serial sum:

==================== ====== =============================================
tracker              thread read at
==================== ====== =============================================
``device_step_cpu``  driver the two places ``device_step`` reads the wall
                            clock (``AsyncDeviceDriver._dispatch``; the
                            sync path, ``StepRuntime._timed_process``, on
                            the client's thread, as all five are there)
``route_cpu``        driver ``route``'s (``PartitionedNFARuntime.dispatch``)
``key_lookup_cpu``   client ``key_lookup``'s (``KeyedWindowRuntime._sealing``;
                            the thread that seals the batch)
``egress_fence_cpu`` driver ``egress_fence``'s (``StepRuntime._fence``)
``egress_decode_cpu`` driver ``collect`` less the fence, as ``egress_decode``
``sink_publish_cpu`` driver ``sink_publish``'s (``_collect_oldest``, lock
                            held)
``client_cycle``     client WALL, seal to seal: ``StepRuntime._emit_batch``
                            of this batch since that of the previous one,
                            where the same thread sealed both (else not
                            recorded): everything the client's thread did
                            for one batch, its own loop and pacing included
``client_cpu``       client that thread's CPU over the same stretch
``driver_cpu``       driver the driver thread's CPU from one batch's last
                            reading (behind its collect, or its publishing;
                            ``_collect_oldest``) to the next's: the
                            histograms' recording, ``on_drained`` and
                            ``_next_action`` included
==================== ====== =============================================

A ``<phase>_cpu`` companion is recorded for every batch its wall tracker is
recorded for, zero included, so the two counts are equal: wall less CPU is
what the thread waited (in ``egress_fence`` for the device; elsewhere for
the GIL, a lock or the scheduler). ``lock_wait`` and ``ring_wait`` are
waits by definition and have none; ``pack`` lies inside ``client_cycle``,
and ``key_lookup`` inside ``pack``.
The clock is as fine as the kernel keeps it. Where thread CPU time is kept
by ticks (the TPU v5e hosts this repo is measured on: ``thread_time``
advances in steps of 10 ms, one read is a system call of about 6 us, and a
thread that blocks for under a millisecond at a time, a poll loop of
``sleep(0.0005)``, still reads about 0.6 of its wall as CPU), one batch's
reading is 0 or a tick and only the MEAN over hundreds of batches reads
true: ``/latency`` gives ``cpu_ms`` as a mean and no percentile of it.

The driver's segments are also spans on the profiler's clock
(``profiler.py``): ``siddhi:seal.pack`` = ``pack``, ``seal.key_lookup`` =
``key_lookup``, ``submit.ring_wait`` = ``ring_wait``, ``dispatch`` =
``device_step``, ``collect.fence`` = ``egress_fence``, ``collect.decode`` =
``egress_decode``, ``deliver.lock`` = ``lock_wait``, ``deliver.publish`` =
``sink_publish``, ``deliver.publish.build`` = ``publish_build``.
"""

from __future__ import annotations

from typing import Optional

PHASES = ("ingress_parse", "ingress_queue", "ring_wait", "fill_wait", "pack",
          "device_step", "egress_fence", "egress_decode", "host_exec",
          "lock_wait", "sink_publish", "dcn_transit", "procmesh_transit")

# a part of a phase told apart: recorded like a phase, outside the serial
# sum (its parent already carries the time)
NESTED = {"route": "device_step", "key_lookup": "pack",
          "decode_full": "egress_decode",
          "hop_drain": "egress_decode", "hop_flush": "egress_decode",
          "publish_build": "sink_publish"}

# a thread's own CPU clock beside the wall clock: phase -> the tracker of
# its thread's CPU seconds over the same stretch
CPU_OF = {"device_step": "device_step_cpu", "route": "route_cpu",
          "key_lookup": "key_lookup_cpu",
          "egress_fence": "egress_fence_cpu",
          "egress_decode": "egress_decode_cpu",
          "sink_publish": "sink_publish_cpu"}
# every tracker of the threads' clocks, outside the serial sum like NESTED:
# the companions, and the two threads whole (``client_cycle`` is the wall of
# ``client_cpu``'s stretch; the driver thread whole has no wall of its own)
THREAD_CLOCKS = tuple(CPU_OF.values()) + ("client_cycle", "client_cpu",
                                          "driver_cpu")

# span stage → phase (unknown stages are host work by default: every
# host-side processor span nests inside the query chain)
_STAGE_PHASE = {
    "parse": "ingress_parse",
    "queue": "ingress_queue",
    "fill-wait": "fill_wait",
    "pack": "pack",
    "device": "device_step",
    "fence": "egress_fence",
    "ingress": "host_exec",
    "query": "host_exec",
    "fleet": "host_exec",
    "sink": "sink_publish",
    "dcn": "dcn_transit",
    "procmesh": "procmesh_transit",
}


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
                         PHASES + tuple(NESTED) + THREAD_CLOCKS}
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
                     route_s: float = 0.0, key_lookup_s: float = 0.0,
                     decode_full_s: float = 0.0,
                     hop_drain_s: float = 0.0, hop_flush_s: float = 0.0,
                     publish_build_s: float = 0.0,
                     step_cpu_s: Optional[float] = None,
                     route_cpu_s: Optional[float] = None,
                     key_lookup_cpu_s: Optional[float] = None,
                     fence_cpu_s: Optional[float] = None,
                     decode_cpu_s: Optional[float] = None,
                     publish_cpu_s: Optional[float] = None,
                     client_cycle_s: Optional[float] = None,
                     client_cpu_s: Optional[float] = None,
                     driver_cpu_s: Optional[float] = None,
                     cause: Optional[str] = None,
                     exemplar=None) -> None:
        """The ``*_cpu_s`` are the recording thread's CPU seconds
        (``time.thread_time``) over the stretch of the wall value beside
        them; None where nobody read that clock (the host tiers)."""
        if n <= 0:
            return
        fill_avg = max(0.0, fill_span_s) / 2.0
        # (tracker, wall seconds, its thread's CPU seconds or None); the
        # first eleven are the serial sum, the rest lie inside pack /
        # device_step / egress_decode and are not segments
        segs = (("ingress_parse", parse_s, None),
                ("fill_wait", fill_avg, None), ("pack", pack_s, None),
                ("ring_wait", ring_s, None), ("ingress_queue", queue_s, None),
                ("device_step", step_s, step_cpu_s),
                ("egress_fence", fence_s, fence_cpu_s),
                ("egress_decode", decode_s, decode_cpu_s),
                ("lock_wait", lock_s, None),
                ("sink_publish", publish_s, publish_cpu_s),
                ("host_exec", host_s, None),
                ("route", route_s, route_cpu_s),
                ("key_lookup", key_lookup_s, key_lookup_cpu_s),
                ("decode_full", decode_full_s, None),
                ("hop_drain", hop_drain_s, None),
                ("hop_flush", hop_flush_s, None))
        total = 0.0
        for phase, v, cpu in segs:
            if v > 0.0:
                self.trackers[phase].record_seconds(v, n, exemplar=exemplar)
                if phase not in NESTED:
                    total += v
                # a companion wherever its wall tracker is, zero included:
                # the two counts stay equal
                if cpu is not None:
                    self.trackers[CPU_OF[phase]].record_seconds(cpu, n)
        if publish_s > 0.0:
            self.trackers["publish_build"].record_seconds(
                publish_build_s, n, exemplar=exemplar)
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
