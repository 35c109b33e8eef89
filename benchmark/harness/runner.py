"""One run of one cell: set-up, warm stretch, window, drain, comparison.

Order of a run (everything before ``open`` is ``setup_s``):

1. events from ``--seed`` into a pool, Python rows built for per-event sends;
2. the app deployed through ``SiddhiManager`` with a ``StreamCallback`` that
   writes into arrays allocated here;
3. two batches sent and drained, so every program the window uses compiles
   (or loads from the persistent cache) now;
4. ``gc.collect()`` then ``gc.freeze()``: the benchmark's long-lived objects
   are not walked again. The collector stays on, default thresholds;
5. the cell's own traffic without a pause: a warm stretch (at least
   ``warm_seconds`` and ``warm_steps`` batches), then the window;
6. drain, read counters and peak memory, shut the app down;
7. the plain reference over every event sent, rows compared, metrics taken.
"""

from __future__ import annotations

import gc
import logging
import os
import shutil
import sys
import time
from typing import NamedTuple

import numpy as np

from . import checks, stats, tracing
from .client import Blocks, Egress, column_sender, drive, row_sender
from .manifest import ROOT, Cell, metric_reader
from .traffic import expand, make_pool

_pc = time.perf_counter
OUT_DIR = os.path.join(ROOT, ".bench_out")


class GcLog:
    """Start and length of every collection, by generation, in arrays
    allocated up front (``gc.callbacks``)."""

    def __init__(self, capacity: int = 1 << 20):
        self.gen = np.zeros(capacity, dtype=np.int64)
        self.t = np.zeros(capacity, dtype=np.float64)
        self.dur = np.zeros(capacity, dtype=np.float64)
        gen, t, dur = (memoryview(a) for a in (self.gen, self.t, self.dur))
        state = self.state = [0, 0.0]

        def cb(phase, info):
            if phase == "start":
                state[1] = _pc()
            else:
                k = state[0]
                if k < capacity:
                    gen[k] = info["generation"]
                    t[k] = state[1]
                    dur[k] = _pc() - state[1]
                    state[0] = k + 1

        self.cb = cb

    def events(self) -> list:
        n = self.state[0]
        return list(zip(self.gen[:n].tolist(), self.t[:n].tolist(),
                        self.dur[:n].tolist()))


class CompileLog:
    """Every trace, lowering or backend compile JAX reports, with the clock
    at its end; persistent-cache hits and misses by count."""

    def __init__(self):
        import jax.monitoring as mon

        self.durations: list = []       # (clock, event, seconds)
        self.counts: dict = {}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, seconds, **_kw):
        if "/compile" in event or "compilation" in event:
            self.durations.append((_pc(), event, float(seconds)))

    def _event(self, event, **_kw):
        self.counts[event] = self.counts.get(event, 0) + 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.durations if t0 <= t < t1)

    def cache_misses(self) -> int:
        return sum(v for k, v in self.counts.items() if "cache_miss" in k)

    def cache_hits(self) -> int:
        return sum(v for k, v in self.counts.items() if "cache_hit" in k)


def _counters(bridge) -> dict:
    """The program's counters the per-layer metrics read, as plain numbers
    (attribute reads; taken at the window's two edges)."""
    probe, driver = bridge.probe, bridge.driver
    out = {"probe.steps": probe.steps, "probe.events": probe.events,
           "causes": dict(probe.flush_causes)}
    if driver is not None:
        out.update({"driver.batches_stepped": driver.batches_stepped,
                    "driver.step_seconds": driver.step_seconds})
    if probe.phases is not None:
        for name, tracker in probe.phases.trackers.items():
            out[f"phase.{name}.count"] = tracker.hist.count
            out[f"phase.{name}.sum"] = tracker.hist.sum
    return out


class Run:
    """What a run left behind, as the metric readers see it."""

    def __init__(self, cell: Cell, device_kind: str):
        self.cell = cell
        self.device_kind = device_kind
        self.batch_capacity = int(cell.config["batch"])
        self.rate = 0.0
        self.i0 = 0
        self.marks: dict = {}
        self.blocks: Blocks | None = None
        self.egress: Egress | None = None
        self.at_open: dict = {}
        self.at_close: dict = {}
        self.gc_events: list = []
        self.trace: dict | None = None
        self.ref: dict | None = None
        self.ref_stamp: np.ndarray | None = None        # per reference row
        self._latency: tuple | None = None

    # -- marks -----------------------------------------------------------------
    @property
    def t_start(self) -> float:
        """Due time of event ``i0`` (the open loop's origin)."""
        return self.marks["start"][0] - self.i0 / self.rate if self.rate \
            else self.marks["start"][0]

    @property
    def t_open(self) -> float:
        return self.marks["open"][0]

    @property
    def t_close(self) -> float:
        return self.marks["close"][0]

    @property
    def i_open(self) -> int:
        return self.marks["open"][1]

    @property
    def i_close(self) -> int:
        return self.marks["close"][1]

    # -- counters ----------------------------------------------------------------
    def delta(self, key: str):
        if key not in self.at_open or key not in self.at_close:
            return None
        return self.at_close[key] - self.at_open[key]

    def delta_causes(self) -> dict:
        a, b = self.at_open.get("causes", {}), self.at_close.get("causes", {})
        return {k: v - a.get(k, 0) for k, v in b.items() if v - a.get(k, 0)}

    # -- the sender's blocks -------------------------------------------------------
    def window_blocks(self, tail: bool = False):
        """(first, count, t0, t1) of the blocks sent in the window (``tail``:
        and after it, to the end of the run)."""
        b = self.blocks
        if b is None or b.n == 0:
            return None
        first, t0 = b.first[:b.n], b.t0[:b.n]
        keep = first >= self.i_open
        if not tail:
            keep &= first < self.i_close
        return first[keep], b.count[:b.n][keep], t0[keep], b.t1[:b.n][keep]

    def window_send_seconds_and_events(self):
        b = self.window_blocks()
        if b is None:
            return 0.0, 0
        _, count, t0, t1 = b
        return float((t1 - t0).sum()), int(count.sum())

    # -- rows ------------------------------------------------------------------------
    def window_row_stamps(self) -> np.ndarray:
        s = self.egress.stamp[:self.egress.n]
        return s[(s >= self.t_open) & (s < self.t_close)]

    def latency_sample(self):
        """(latency ms, due time) of every reference row whose last
        contributing event was due in the window; None in a closed loop.
        Taken once the reference has run, and kept."""
        if self._latency is None and self.rate and self.ref is not None:
            last = self.ref["last_event"]
            keep = (last >= self.i_open) & (last < self.i_close)
            if keep.any():
                due = self.t_start + last[keep] / self.rate
                self._latency = (stats.latencies_ms(self.ref_stamp[keep],
                                                    due), due)
        return self._latency

    # -- trace -----------------------------------------------------------------------
    def device_seconds_per_batch(self):
        t = self.trace
        return t["busy_s"] / t["steps"] if t and t["steps"] else None

    def idle_share_pct(self):
        t = self.trace
        if not t or not t["window_s"]:
            return None
        return (1.0 - t["busy_s"] / t["window_s"]) * 100.0


class Sizes(NamedTuple):
    batch: int          # events of a full device batch
    closed: bool        # closed loop (else open, at `rate`)
    rate: float         # events/s of an open loop, 0 in a closed one
    warm_s: float
    warm_steps: int
    pool_n: int         # events drawn from the seed
    columnar: bool      # send_columns (else per-event send)
    block: int          # events between two clock reads of the sender
    max_events: int     # what the sender's and the rows' arrays must hold
    rows_cap: int


def plan(cell: Cell, seconds: float, rehearsal: bool) -> Sizes:
    """The sizes of one run, from the cell's own files. A rehearsal shrinks
    the pool, the warm stretch and the rate (the configuration's ``small``
    block has shrunk the engine's sizes before, ``Cell.shrunk``)."""
    cfg, mix = cell.config, cell.traffic
    batch = int(cfg["batch"])
    closed = mix["loop"] == "closed"
    rate = 0.0 if closed else float(mix["rate_eps"])
    warm_s, warm_steps = float(mix["warm_seconds"]), int(mix["warm_steps"])
    pool_n = int(cfg["pool_events"])
    if rehearsal:
        warm_s, warm_steps, pool_n = 0.3, 1, min(pool_n, 1 << 16)
        rate = 0.0 if closed else min(rate, 1500.0)
    columnar = cfg["ingress"] == "columns"
    block = int(mix["chunk_rows"] if columnar else mix["block_events"])
    if columnar and pool_n % block:
        raise ValueError(f"pool of {pool_n} is no multiple of the chunk "
                         f"({block})")
    total_s = warm_s + seconds + 5.0
    max_events = int((float(mix["max_rate_eps"]) if closed else rate)
                     * total_s) + 4 * batch + warm_steps * batch
    rows_cap = int(max_events * float(cfg["rows_per_event_max"])) + 1024
    return Sizes(batch, closed, rate, warm_s, warm_steps, pool_n, columnar,
                 block, max_events, rows_cap)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, rehearsal: bool = False,
             control: bool = False, after_deploy=None, say=print):
    """Returns ``(result, rc)``; ``result`` is None where no result may be
    printed (no accelerator, fewer chips than the cell asks for)."""
    import jax

    if rehearsal:
        cell = cell.shrunk()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not rehearsal and (platform != "tpu" or len(devices) < cell.chips):
        print(f"benchmark: JAX found platform '{platform}' ({kind} x"
              f"{len(devices)}); cell '{cell.name}' needs {cell.chips} TPU "
              f"chip(s). No result.", file=sys.stderr)
        return None, 1
    try:
        from siddhi_tpu import SiddhiManager, StreamCallback
        from siddhi_tpu.tpu.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"benchmark: {e}: the program is not beside the benchmark "
              f"(run from the root of a siddhi_tpu checkout). No result.",
              file=sys.stderr)
        return None, 1

    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    warnings = checks.WarningCounter()
    logging.getLogger("siddhi_tpu").addHandler(warnings)
    t_import = _pc()

    cfg, mix = cell.config, cell.traffic
    (batch, closed, rate, warm_s, warm_steps, pool_n, columnar, block,
     max_events, rows_cap) = plan(cell, seconds, rehearsal)

    run = Run(cell, kind)
    run.rate = rate
    pool = make_pool(cfg, cell.config_name, mix, seed, pool_n)
    names = cfg["stream"]["columns"]
    run.egress = egress = Egress([tuple(c) for c in cfg["output"]["columns"]],
                                 rows_cap)
    run.blocks = blocks = Blocks(max_events // (1 if not closed and
                                                not columnar else block) + 64)
    gclog = GcLog()
    t_data = _pc()

    manager = SiddhiManager()
    try:
        rt = manager.create_siddhi_app_runtime(cell.app_text, playback=True)
        rt.add_callback(cfg["output"]["stream"],
                        StreamCallback(egress.callback()))
        rt.start()
        if after_deploy is not None:
            after_deploy(rt)
        bridge = rt.device_bridges[0] if rt.device_bridges else None
        handler = rt.input_handler(cfg["stream"]["id"])
        base_ts = int(cfg["base_timestamp"])
        send_block = (column_sender if columnar else row_sender)(
            handler, pool, names, base_ts)
        t_deploy = _pc()

        # every program the window uses, compiled or loaded now: two full
        # batches and the drain's partial one (same shapes)
        i0 = 0
        while i0 < 2 * batch:
            send_block(i0, block)
            i0 += block
        rt.flush_device()
        t_compiled = _pc()
        run.i0 = i0

        annotation = None
        trace_dir = os.path.join(OUT_DIR, "trace-" + cell.name)
        if trace:
            from jax.profiler import TraceAnnotation
            annotation = TraceAnnotation
            shutil.rmtree(trace_dir, ignore_errors=True)

        gc.collect()
        gc.freeze()
        gc.callbacks.append(gclog.cb)
        t_frozen = _pc()

        def on_mark(name):
            if name == "open" and bridge is not None:
                run.at_open = _counters(bridge)
            elif name == "close" and bridge is not None:
                run.at_close = _counters(bridge)
            elif name == "trace_on":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)

        trace_s = min(float(mix["trace_seconds"]), seconds / 2.0)
        run.marks = drive(
            send_block, blocks, seconds=seconds, warm_seconds=warm_s,
            warm_events=warm_steps * batch, block=block, rate=rate,
            chunked=columnar, tick_s=float(mix.get("tick_ms", 0.5)) / 1e3,
            catchup=float(mix.get("catchup_factor", 0.0)),
            tail_batch=batch, i0=i0,
            outstanding=int(mix.get("max_outstanding_batches", 0)) * batch,
            stepped=(lambda: bridge.probe.events) if bridge is not None
            else None,
            trace_at=(seconds - trace_s) if trace else None,
            on_mark=on_mark, annotate=annotation)
        if trace and "trace_on" in run.marks:
            jax.profiler.stop_trace()
        rt.flush_device()
        t_drained = _pc()
        gc.callbacks.remove(gclog.cb)
        sent = run.marks["stop"][1]

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell.chips])
        numbers = checks.served_numbers(rt, sent, platform)
    finally:
        manager.shutdown()
        logging.getLogger("siddhi_tpu").removeHandler(warnings)
    run.gc_events = gclog.events()

    say(f"device: {platform} {kind} x{len(devices)}  jax {jax.__version__}  "
        f"compile_cache_dir: {cache_dir}")
    marks = run.marks
    if "open" not in marks or "close" not in marks:
        print(f"benchmark: the window never {'closed' if 'open' in marks else 'opened'}"
              f" (marks {sorted(marks)}): the sender's block buffer "
              f"overflowed; no result", file=sys.stderr)
        return None, 1

    # ---- the plain reference, after the window and the program's shutdown
    t_ref0 = _pc()
    stream = expand(pool, sent)
    ref = cell.reference.reference(cfg, stream, sent)
    cmp_ = checks.compare_rows(ref, egress.columns(), egress.n)
    t_ref1 = _pc()
    mapping = cmp_.pop("map")
    run.ref = ref
    delivered = mapping >= 0
    run.ref_stamp = np.full(len(ref["last_event"]), t_drained)
    run.ref_stamp[mapping[delivered]] = egress.stamp[:egress.n][delivered]

    numbers.update(cmp_)
    numbers["egress_buffer_overflow"] = egress.overflowed
    numbers["compiles_in_window"] = compiles.between(run.t_open, run.t_close)
    numbers["warnings_logged"] = len(warnings.records)
    limits = {k: 0 for k in numbers}
    correct = all(numbers[k] <= limits[k] for k in numbers)
    if not len(ref["last_event"]):
        correct = False     # comparing nothing with nothing proves nothing
        numbers["reference_rows_none"], limits["reference_rows_none"] = 1, 0

    if trace and "trace_on" in marks:
        try:
            run.trace = tracing.reduce(
                tracing.extract(tracing.newest_xplane(trace_dir)))
        except ValueError as e:
            if not rehearsal:   # a CPU has no device plane; a chip must
                raise
            say(f"trace: {e} (a rehearsal on a CPU has no device plane)")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- metrics
    window = run.t_close - run.t_open
    setup_s = run.t_open - t_process
    e2e: dict = {}
    stamps = egress.stamp[:egress.n][delivered]
    row_last = ref["last_event"][mapping[delivered]]
    for m in cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            e2e[name] = setup_s
        elif name == "throughput_eps":
            e2e[name] = stats.throughput_eps(stamps, row_last, run.t_open,
                                             run.t_close)
        elif name in ("latency_p50_ms", "latency_p95_ms"):
            lat = run.latency_sample()
            if lat is not None:
                e2e[name] = stats.percentile(
                    lat[0], 50 if name == "latency_p50_ms" else 95)
        else:
            raise KeyError(f"no code takes end-to-end metric '{name}'")
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    layer: dict = {}
    if trace:
        for m in cell.per_layer:
            value = metric_reader(m["name"])(run)
            if value is not None:
                layer[m["name"]] = float(value)

    # ---- earlier lines: what PERF.md wants and the result line does not
    say(f"setup_s parts: imports {t_import - t_process:.2f}  data "
        f"{t_data - t_import:.2f}  deploy {t_deploy - t_data:.2f}  compile "
        f"{t_compiled - t_deploy:.2f}  freeze {t_frozen - t_compiled:.2f}  "
        f"warm {run.t_open - t_frozen:.2f}  = {setup_s:.2f}")
    say(f"compile: cache_misses {compiles.cache_misses()} cache_hits "
        f"{compiles.cache_hits()} "
        f"({'cold: compiled' if compiles.cache_misses() else 'warm: every program from the cache'})"
        f"  compile events in window {numbers['compiles_in_window']}")
    full = [(t, d) for g, t, d in run.gc_events
            if g == 2 and run.t_open <= t < run.t_close]
    allg = [d for g, t, d in run.gc_events if run.t_open <= t < run.t_close]
    say(f"gc in window: collections {len(allg)} pause {sum(allg):.4f}s  "
        f"full (gen 2) {len(full)} pause {sum(d for _, d in full):.4f}s "
        f"at {[round(t - run.t_open, 2) for t, _ in full][:8]}")
    if closed and stamps.size:
        edges = np.arange(run.t_open, run.t_close + 1e-9, 5.0)
        done = [stats.events_done(stamps, row_last, t) for t in edges]
        say("throughput by 5 s slice (events/s): "
            + " ".join(f"{(b - a) / 5.0:.0f}" for a, b in zip(done, done[1:])))
        say(f"events sent in window / s: "
            f"{(run.i_close - run.i_open) / window:.0f}")
    if not closed:
        lat = run.latency_sample()
        if lat is not None:
            backlog = [run.marks[k][1] - at.get("probe.events", 0)
                       for k, at in (("open", run.at_open),
                                     ("close", run.at_close))]
            say(f"latency sample: {lat[0].size} rows due in the window of "
                f"{window:.2f}s at {rate:.0f} events/s: p50 "
                f"{stats.percentile(lat[0], 50):.2f} p95 "
                f"{stats.percentile(lat[0], 95):.2f} p99 "
                f"{stats.percentile(lat[0], 99):.2f} ms; events sent and "
                f"not yet stepped at open {backlog[0]}, at close "
                f"{backlog[1]}")
    if blocks.n:
        # the backlog by second of the run, and the longest silences
        bt, bo = blocks.t0[:blocks.n], blocks.outstanding[:blocks.n]
        sec = (bt - marks["start"][0]).astype(np.int64)
        per_s = [int(bo[sec == k].max()) if (sec == k).any() else -1
                 for k in range(int(sec.max()) + 1)]
        say(f"events sent and not yet stepped, max by second from the warm "
            f"stretch's start (window opens at "
            f"+{run.t_open - marks['start'][0]:.1f}s): {per_s}")
        send_len = blocks.t1[:blocks.n] - bt
        k = int(send_len.argmax())
        in_window = run.window_row_stamps()
        gaps = np.diff(in_window) if in_window.size > 1 else np.zeros(1)
        g = int(gaps.argmax())
        say(f"longest block of sends {send_len[k] * 1e3:.1f} ms at "
            f"+{bt[k] - run.t_open:.2f}s; longest silence between rows in "
            f"the window {gaps[g] * 1e3:.1f} ms at "
            f"+{(in_window[g] - run.t_open) if in_window.size else 0:.2f}s "
            f"(from the window's open)")
    say(f"window {window:.3f}s  events sent {sent}  rows delivered "
        f"{egress.n}  reference rows {len(ref['last_event'])}  reference+"
        f"compare {t_ref1 - t_ref0:.2f}s  drain {t_drained - marks['stop'][0]:.3f}s"
        f"  memory_peak_bytes {peak}")
    if warnings.records:
        say("warnings: " + " | ".join(warnings.records[:5]))
    ctl = None
    if control:
        import ml_dtypes
        low = cell.reference.reference(cfg, stream, sent,
                                       dtype=ml_dtypes.bfloat16)
        ctl = checks.compare_rows(ref, low["columns"],
                                  len(low["last_event"]))
        ctl.pop("map")
        say(f"control (reference in bfloat16 in the program's place): {ctl} "
            f"-> {'not correct' if any(ctl.values()) else 'CORRECT: the comparison cannot tell'}")

    compared = {k: [numbers[k], limits[k]] for k in numbers}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct),
              "attempted": int(len(ref["last_event"])),
              "failed": int(numbers["rows_missing"] + numbers["rows_wrong"]
                            + numbers["rows_extra"])}
    chosen = layer if trace else e2e
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in chosen.items()}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["device"] = device
    if ctl is not None:
        result["control"] = ctl
    result["compared"] = compared
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    return result, 0
