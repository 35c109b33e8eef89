"""Benchmark: north-star pattern workload (BASELINE.json).

Workload: 8-state rising-chain pattern (``every e1 -> e2[v>e1.v] -> ... -> e8``,
``within``) over a synthetic IoT stream, 64-way partitioned — BASELINE.json
configs #3/#5 shape. Reports:

- steady-state device throughput (events/sec) of the compiled, partitioned NFA;
- **p99 detection latency** at an offered arrival rate (events get scheduled
  arrival times at ``BENCH_OFFERED_EVPS``; a batch is released when its last
  event has arrived; per-event latency = batch completion − scheduled arrival);
- the same app on the host interpreter as the CPU baseline. The baseline is
  this repo's own single-threaded Python interpreter (the reference publishes
  no numbers — BASELINE.md — and no JVM exists in this image), so
  ``vs_baseline`` flatters the device vs a real JVM; the JSON says so.

Process layout: a chip belongs to one process at a time, so this process
never imports jax — a parent that had touched JAX would hold the chip and
starve every child of it. All device/host work runs in subprocesses with hard
deadlines, one at a time, and the device bench is split into PER-PHASE
subprocesses (smoke → compile → throughput → latency → oracle), each under
its own deadline, gated on the smoke probe, sharing compiled programs through
the JAX persistent compilation cache (``siddhi_tpu/tpu/compile_cache.py``) —
a hung phase costs one phase, never the round, and the final JSON names the
phase that died (``device_phases``). Every deadline is clamped to a TOTAL
wall-clock budget (``BENCH_TOTAL_BUDGET_S``), and the final JSON line is
emitted with reserve headroom no matter what, with ``device_ok``/``error``
flags instead of a stack trace. The ingest hot path is the C++ data-loader
(``native/ingress.cpp``) when a toolchain exists; ``"ingress"`` in the JSON
records which path was measured.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}. The
exit status is non-zero when the smoke child found no accelerator or any
device phase did not end ``ok`` — the host lines in the JSON are still there,
but a run that measured no chip is not a successful device benchmark.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

N_STATES = int(os.environ.get("BENCH_STATES", 8))
N_PARTITIONS = int(os.environ.get("BENCH_PARTITIONS", 64))
LANE_BATCH = int(os.environ.get("BENCH_LANE_BATCH", 2048))
# blocked-kernel creation budget: compacting per-batch creations to K caps
# each stage grid at [B, C+K] instead of the quadratic [B, C+B]; LB=2048 /
# CAP=320 is the best sweep point found on this workload (~10% seed
# selectivity, zero dropped partials); drops are counted in the JSON if a
# hotter workload overflows the budget
CREATION_CAP = int(os.environ.get("BENCH_CREATION_CAP", 320))
# latency mode runs deadline-flush windows (~WINDOW events per step spread
# over partially-filled lanes); a right-sized lane batch keeps the static
# step cost proportional to the window instead of paying full-throughput
# shapes for quarter-filled lanes
LAT_WINDOW = int(os.environ.get("BENCH_LAT_WINDOW", 8192))
LAT_LANE_BATCH = int(os.environ.get(
    "BENCH_LAT_LANE_BATCH", max(64, 2 * LAT_WINDOW // N_PARTITIONS)))
LAT_CREATION_CAP = int(os.environ.get(
    "BENCH_LAT_CREATION_CAP", max(64, LAT_LANE_BATCH // 4)))
# detection-latency SLO the closed-loop search reports against
LAT_BUDGET_MS = float(os.environ.get("BENCH_LAT_BUDGET_MS", 100.0))
# BENCH_ADAPTIVE: the flow subsystem's AIMD controller
# (siddhi_tpu/flow/adaptive_batch.py) in LATENCY MODE picks the
# deadline-flush window from the observed-p99 step latency against
# BENCH_LAT_BUDGET_MS instead of the hand-tuned BENCH_LAT_WINDOW; the
# chosen size ships in the JSON as "adaptive_batch_size" and the paced
# sweep runs at the chosen window ("latency_mode" line). Default ON —
# BENCH_ADAPTIVE=0 pins the static window.
ADAPTIVE = os.environ.get("BENCH_ADAPTIVE", "1") != "0"
# BENCH_METRICS=1: the host child enables BASIC statistics and the final
# JSON line carries a "metrics_snapshot" (percentile latencies, gauges)
# alongside the timings; default output stays byte-identical
BENCH_METRICS = os.environ.get("BENCH_METRICS", "") == "1"
ADAPTIVE_TARGET_MS = float(
    os.environ.get("BENCH_ADAPTIVE_TARGET_MS", LAT_BUDGET_MS / 2))
SLOT_CAP = int(os.environ.get("BENCH_SLOT_CAP", 64))
N_DEVICES_KEYS = 256          # distinct device ids in the synthetic stream
DEVICE_EVENTS = int(os.environ.get("BENCH_EVENTS", 2_000_000))
BASELINE_EVENTS = int(os.environ.get("BENCH_BASELINE_EVENTS", 20_000))
# oracle cross-check segment: both engines process this identical prefix and
# the parent asserts their match counts agree (VERDICT r3 item 9)
ORACLE_EVENTS = max(int(os.environ.get("BENCH_ORACLE_EVENTS", 200_000)),
                    BASELINE_EVENTS)
OFFERED_EVPS = int(os.environ.get("BENCH_OFFERED_EVPS", 1_000_000))
# columnar host fast path (@app:host_batch): micro-batch chunk size + NFA
# lane count for the host child's vectorized line
HOST_CHUNK = int(os.environ.get("BENCH_HOST_CHUNK", 8192))
HOST_LANES = int(os.environ.get("BENCH_HOST_LANES", 24))
# multi-tenant fleet scenario (--fleet-child): K tenant apps of one rule
# template over a shared feed, delivered as fine-grained per-tenant chunks
# (the multiplexed-ingress regime thousands-of-apps serving implies); the
# SAME apps run once under @app:fleet (shared plan, cross-app lane batching)
# and once per-app solo on the columnar host tier
TENANTS = int(os.environ.get("BENCH_TENANTS", 16))
TENANT_FEED = int(os.environ.get("BENCH_TENANT_FEED", 12_000))
TENANT_CHUNK = int(os.environ.get("BENCH_TENANT_CHUNK", 16))
FLEET_BATCH = int(os.environ.get("BENCH_FLEET_BATCH", 8192))
FLEET_PATTERN_FEED = int(os.environ.get("BENCH_FLEET_PATTERN_FEED", 4_000))
# zero-object edge line (--edge-child): raw CSV transport bytes parsed
# straight into columns (native ingress when a toolchain exists) and fed
# through send_columns into the columnar host tier — measures host
# bytes-in → rows-out with NO per-event Python objects (asserted)
EDGE_EVENTS = int(os.environ.get("BENCH_EDGE_EVENTS", 1_000_000))
EDGE_CHUNK_BYTES = int(os.environ.get("BENCH_EDGE_CHUNK_BYTES", 1 << 20))
EDGE_BATCH = int(os.environ.get("BENCH_EDGE_BATCH", 65536))
# parallel columnar host tier line: the bench pattern corpus under
# @app:host_batch(workers=W) for W in {1,2,4}
EDGE_PAR_EVENTS = int(os.environ.get("BENCH_EDGE_PAR_EVENTS", 200_000))
EDGE_PAR_BATCH = int(os.environ.get("BENCH_EDGE_PAR_BATCH", 32768))
EDGE_PAR_LANES = int(os.environ.get("BENCH_EDGE_PAR_LANES", 16))
# SLO-autopilot chaos storm (--slo-child): K fleet tenants with declared
# SLO classes, one best-effort tenant bursting at SLO_BURST× its share —
# the closed loop must keep premium p99 inside BENCH_SLO_BUDGET_MS while
# the burster's overflow sheds (premium sheds must be ZERO)
SLO_TENANTS = int(os.environ.get("BENCH_SLO_TENANTS", 16))
SLO_FEED = int(os.environ.get("BENCH_SLO_FEED", 24_000))
SLO_CHUNK = int(os.environ.get("BENCH_SLO_CHUNK", 32))
SLO_BURST = int(os.environ.get("BENCH_SLO_BURST", 10))
# the declared premium budget: the ROADMAP's p99<100ms detection bar —
# tight enough that the oversized opening window violates it, loose
# enough that a single container scheduler stall (~50-90ms observed on
# the 2-cpu CI box) cannot fail a converged run
SLO_BUDGET_MS = float(os.environ.get("BENCH_SLO_BUDGET_MS", 100.0))
# initial window deliberately oversized for the offered rate: the storm
# must OPEN in violation (fill-wait past the budget) so the report shows
# the loop closing it, not a scenario that was never stressed
SLO_BATCH = int(os.environ.get("BENCH_SLO_BATCH", 65536))
# mesh-fabric scenario (--mesh-child): the tenant population placed across
# a forced-host multi-device mesh (XLA_FLAGS
# --xla_force_host_platform_device_count=N, the MULTICHIP_r05 setup) —
# placement quality (shape-locality vs random: compiled programs per host,
# lanes per step), scaling curves of the Kleene anomaly workload over mesh
# sizes, a live migration under sustained ingest, and a host leave/join
# elasticity cycle, all exactly-once vs solo oracles
MESH_HOSTS = int(os.environ.get("BENCH_MESH_HOSTS", 8))
MESH_PLACE_TENANTS = int(os.environ.get("BENCH_MESH_PLACE_TENANTS", 1024))
MESH_SHAPES = int(os.environ.get("BENCH_MESH_SHAPES", 8))
MESH_PLACE_FEED = int(os.environ.get("BENCH_MESH_PLACE_FEED", 256))
MESH_SCALE_TENANTS = int(os.environ.get("BENCH_MESH_SCALE_TENANTS", 2))
MESH_FEED = int(os.environ.get("BENCH_MESH_FEED", 4000))
MESH_CHUNK = int(os.environ.get("BENCH_MESH_CHUNK", 64))
MESH_DEADLINE_S = int(os.environ.get("BENCH_MESH_DEADLINE_S", 900))
# gray-failure gauntlet (ISSUE 19, the MULTICHIP_r10 line): feed length
# for the wedged-worker phase — two kleene tenants on separate host
# processes, one worker wedged mid-feed (alive, heartbeating, op-stalling)
GRAY_FEED = int(os.environ.get("BENCH_GRAY_FEED", 2000))
GRAY_DEADLINE_S = int(os.environ.get("BENCH_GRAY_DEADLINE_S", 600))
HOST_DEADLINE_S = int(os.environ.get("BENCH_HOST_DEADLINE_S", 300))
FLEET_DEADLINE_S = int(os.environ.get("BENCH_FLEET_DEADLINE_S", 300))
SLO_DEADLINE_S = int(os.environ.get("BENCH_SLO_DEADLINE_S", 240))
EDGE_DEADLINE_S = int(os.environ.get("BENCH_EDGE_DEADLINE_S", 300))
SMOKE_DEADLINE_S = int(os.environ.get("BENCH_SMOKE_DEADLINE_S", 60))
# (the r1-r4 escalating probe ladder is gone: it is what starved r4's
# device attempt — see VERDICT r4 "what's weak" item 3)
# per-phase device-child deadlines (VERDICT r4/r5/r6: the monolithic device
# child wedged and cost THE WHOLE ROUND of device evidence — each phase now
# runs in its own subprocess under its own deadline, compiled programs are
# shared across phases via the JAX persistent compilation cache, and the
# parent records per-phase status so a wedge costs exactly one phase)
PHASE_DEADLINES = (
    ("compile", int(os.environ.get("BENCH_COMPILE_DEADLINE_S", 300))),
    ("throughput", int(os.environ.get("BENCH_THROUGHPUT_DEADLINE_S", 420))),
    ("latency", int(os.environ.get("BENCH_LATENCY_DEADLINE_S", 300))),
    ("oracle", int(os.environ.get("BENCH_ORACLE_DEADLINE_S", 240))),
)
# hard budget for the WHOLE bench process (VERDICT r4 item 1: the r4 probe
# ladder summed 60+180+360+540s and the driver killed the parent before the
# emit-always path could fire — rc=124, no JSON). Every child deadline is
# clamped to the remaining budget; the final JSON line is printed with at
# least RESERVE_S of headroom no matter which child hangs.
TOTAL_BUDGET_S = int(os.environ.get("BENCH_TOTAL_BUDGET_S", 1200))
RESERVE_S = 15
_T0 = time.monotonic()


def _remaining() -> float:
    return TOTAL_BUDGET_S - (time.monotonic() - _T0) - RESERVE_S


DEBUG_LOG = os.environ.get("BENCH_DEBUG_LOG") \
    or os.path.join(REPO, "BENCH_DEBUG.log")


def make_app() -> str:
    """Per-device 8-state rising chain, 64-way partitioned (config #5 shape).
    The SAME partitioned app runs on both engines."""
    # selective seed (top-10% spike starts a chain) + bounded window keep the
    # partial-match population finite — "parity selectivity": both engines see
    # the identical app and data
    states = " -> ".join(
        f"e{i}=S[v > e{i-1}.v]" if i > 1 else "e1=S[v > 90.0]"
        for i in range(1, N_STATES + 1))
    sel = ", ".join(f"e{i}.v as v{i}" for i in range(1, N_STATES + 1))
    return f"""
define stream S (dev string, v double);
partition with (dev of S)
begin
from every {states} within 4000
select {sel} insert into Alerts;
end;
"""


def gen_events(n: int, seed: int = 42):
    """Synthetic IoT stream: per-device noisy ramps (parity-selectivity-ish:
    rising chains occur but don't explode)."""
    import random

    rng = random.Random(seed)
    out = []
    for i in range(n):
        dev = f"dev{rng.randrange(N_DEVICES_KEYS)}"
        v = round(rng.uniform(0.0, 100.0), 3)
        out.append((dev, v, 1_000_000 + i))
    return out


def _envelope_percentile(envelopes, q: float) -> float:
    """Population quantile from per-batch latency envelopes.

    Each batch contributes ``n`` events whose latencies are ~uniform on
    [lo, hi]; interpolate each envelope at evenly spaced points weighted by
    its population share, then take the weighted quantile."""
    import numpy as np

    samples, weights = [], []
    for lo, hi, n in envelopes:
        pts = min(max(int(n), 1), 64)
        xs = np.linspace(lo, hi, pts)
        samples.append(xs)
        weights.append(np.full(pts, n / pts))
    s = np.concatenate(samples)
    w = np.concatenate(weights)
    order = np.argsort(s)
    s, w = s[order], w[order]
    cw = np.cumsum(w)
    return float(s[np.searchsorted(cw, q * cw[-1], side="left")])


# ---------------------------------------------------------------------------
# child: device benchmark (the one process that holds the chip)
# ---------------------------------------------------------------------------

def child_smoke() -> None:
    """Minimal liveness check: backend init + ONE tiny jitted op, and the
    platform JAX landed on (the parent gates the device phases on it)."""
    import time as _t
    t0 = _t.perf_counter()
    import jax
    t_import = _t.perf_counter() - t0
    t0 = _t.perf_counter()
    dev = jax.devices()[0]
    t_init = _t.perf_counter() - t0
    import jax.numpy as jnp
    t0 = _t.perf_counter()
    y = (jnp.ones((8, 8), jnp.float32) + 1.0)
    y.block_until_ready()
    t_op = _t.perf_counter() - t0
    print(json.dumps({"platform": jax.default_backend(), "device": str(dev),
                      "import_s": round(t_import, 2),
                      "init_s": round(t_init, 2), "op_s": round(t_op, 2)}))


def _phase_hook(phase: str) -> None:
    """Test hooks for the bench-hardening pins: BENCH_PHASE_KILL=<phase>
    SIGKILLs this child at phase start (a simulated wedge-kill the parent
    must survive with a per-phase status); BENCH_PHASE_WEDGE=<phase> hangs
    it (the per-phase deadline must contain the damage)."""
    import signal
    if os.environ.get("BENCH_PHASE_KILL") == phase:
        os.kill(os.getpid(), signal.SIGKILL)
    if os.environ.get("BENCH_PHASE_WEDGE") == phase:
        time.sleep(100_000)


def _stack_lanes(batches, first_idx, last_idx, count=None):
    """Lane batches (wire format) -> one [P, ...] device feed."""
    import numpy as np
    return {
        "cols": {k: np.stack([bt["cols"][k] for bt in batches])
                 for k in batches[0]["cols"]},
        "tag": np.stack([bt["tag"] for bt in batches]),
        "ts": np.stack([bt["ts"] for bt in batches]),
        "ts_base": np.array([bt["ts_base"] for bt in batches],
                            dtype=np.int64),
        "counts": np.array([bt["count"] for bt in batches],
                           dtype=np.int32),
        "count": count if count is not None
                 else sum(int(bt["count"]) for bt in batches),
        "first_idx": first_idx,     # oldest event in the batch
        "last_idx": last_idx,       # newest event in the batch
    }


def _make_runtime(lane_batch: int, creation_cap: int):
    from siddhi_tpu.tpu.partition import PartitionedNFARuntime
    return PartitionedNFARuntime(
        make_app(), num_partitions=N_PARTITIONS, key_attr="dev",
        slot_capacity=SLOT_CAP, lane_batch=lane_batch, mesh=None,
        creation_cap=creation_cap)


def _run_once(rt, state, b):
    return rt.vstep(state, b["cols"], b["tag"], b["ts"], b["ts_base"],
                    b["counts"])


def _fence(state) -> int:
    """Forces real completion at a timing boundary and returns the match
    count. Timed against ``block_until_ready`` on a TPU v5 lite (PR 21, 8
    steps of the bench shape, medians): ``block_until_ready`` waited 54.5 ms
    and a ``device_get`` after it 0.6 ms; ``device_get`` alone waited
    54.7 ms. Both fence; this one is kept because callers use the count."""
    import numpy as np
    import jax
    return int(np.sum(jax.device_get(state["matches"])))


class _Packer:
    """Reusable ingest front end for the device phases: ``iter_feeds()``
    yields stacked [P, ...] device feeds, repeatably (the overlap phase
    re-packs in a producer thread while the device steps).

    Path A (preferred): the C++ data-loader in the measured path (VERDICT
    r4 item 4) — raw CSV bytes -> parse -> dict-encode -> crc32 lane
    routing -> SoA pack, all native; Python only stacks lane buffers.
    Path B: vectorized python pack (dictionary-encode on distinct values,
    ONE stable argsort routing, bulk slice-copies into wire builders)."""

    def __init__(self, rt, events):
        self.rt = rt
        self.events = events
        self.ingress = "python"
        self._csv = None
        self._routed = None
        try:
            from siddhi_tpu.native import native_available
            if native_available():
                rt.enable_native_ingress()
                self.ingress = "native"
                # the transport payload (what a socket would deliver);
                # building it is data *generation*, not ingest — untimed
                self._csv = "".join(
                    f"{dev},{v},{ts}\n"
                    for dev, v, ts in events).encode()
        except Exception as e:                             # pragma: no cover
            self.ingress = "python"     # may fail AFTER the flag flipped
            self._csv = None
            print(f"# native ingress unavailable ({e}); python pack "
                  f"fallback", file=sys.stderr)

    def iter_feeds(self):
        if self.ingress == "native":
            yield from self._iter_native()
        else:
            yield from self._iter_python()

    def _iter_native(self):
        rt, data = self.rt, self._csv
        pos, n = 0, len(data)
        while pos < n:
            pos += rt._ning.ingest_csv(data, ts_last=True, offset=pos)
            yield rt.emit_native_feed()
        if any(rt._ning.lane_len(ln) for ln in range(N_PARTITIONS)):
            yield rt.emit_native_feed()

    def _iter_python(self):
        import numpy as np
        if self._routed is None:
            devs = np.array([e[0] for e in self.events], dtype="U8")
            vals = np.array([e[1] for e in self.events])
            tss = np.array([e[2] for e in self.events], dtype=np.int64)
            self._routed = self.rt.partition_columns(
                "S", {"dev": devs, "v": vals}, tss)
        lane_cols, lane_ts = self._routed
        total = len(self.events)
        pos = [0] * N_PARTITIONS
        done = 0
        while done < total:
            batches = []
            for lane in range(N_PARTITIONS):
                b = self.rt.builders[lane]
                take = b.append_many("S", lane_cols[lane], lane_ts[lane],
                                     start=pos[lane])
                pos[lane] += take
                done += take
                batches.append(b.emit())
            yield _stack_lanes(batches, 0, 0)


def _pack_windowed(rt, evs, window):
    """Contiguous-arrival windows -> padded lane batches (deadline-flush
    shape). Cuts a window early if any lane fills."""
    out = []
    s = 0
    while s < len(evs):
        n = 0
        for dev, v, ts in evs[s: s + window]:
            b = rt.builders[rt.lane_of(dev)]
            if b.full:
                break
            b.append("S", [dev, v], ts)
            n += 1
        batches = [b.emit() for b in rt.builders]
        out.append(_stack_lanes(batches, s, s + n - 1, count=n))
        s += n
    return out


def _phase_compile() -> dict:
    """Backend init + jit compile of BOTH step shapes (throughput lanes and
    latency lanes) over a small prefix, timed separately, plus the d2h
    round-trip and steady-state step time. The programs compiled here land
    in the persistent compilation cache (``child_device``) and are reused by
    the later phases — a phase dying after this one still leaves it warm."""
    import time as _t
    out = {}
    prefix = gen_events(min(DEVICE_EVENTS, 2 * N_PARTITIONS * LANE_BATCH))
    rt = _make_runtime(LANE_BATCH, CREATION_CAP)
    packer = _Packer(rt, prefix)
    feed = next(packer.iter_feeds())
    t0 = _t.perf_counter()
    state, ys = _run_once(rt, rt.state, feed)
    _fence(state)
    out["compile_s"] = round(_t.perf_counter() - t0, 3)
    # d2h round-trip of one scalar with nothing in flight: reported so
    # step-time can be read net of the fence's own cost
    t0 = _t.perf_counter()
    _fence(state)
    out["roundtrip_ms"] = round((_t.perf_counter() - t0) * 1e3, 3)
    # steady-state single-step time, fenced (VERDICT r2 item 2)
    t0 = _t.perf_counter()
    state, ys = _run_once(rt, state, feed)
    _fence(state)
    out["step_ms"] = round((_t.perf_counter() - t0) * 1e3, 3)
    # latency-mode shapes (deadline-flush lane batch)
    lrt = _make_runtime(LAT_LANE_BATCH, LAT_CREATION_CAP)
    wfeed = _pack_windowed(lrt, prefix[: LAT_WINDOW], LAT_WINDOW)[0]
    t0 = _t.perf_counter()
    lstate, ys = _run_once(lrt, lrt.state, wfeed)
    _fence(lstate)
    out["latency_compile_s"] = round(_t.perf_counter() - t0, 3)
    print(f"# compile: throughput {out['compile_s']}s (step "
          f"{out['step_ms']}ms, roundtrip {out['roundtrip_ms']}ms), "
          f"latency shapes {out['latency_compile_s']}s", file=sys.stderr)
    return out


def _phase_throughput() -> dict:
    """Unthrottled steady-state rate + the pack/step overlap line (the
    double-buffered pipeline's operating mode: a producer thread packs
    batch N+1 into a 2-deep ring while the device steps batch N; the fence
    sits ONLY at the end — the egress edge)."""
    import numpy as np
    import jax
    out = {}
    events = gen_events(DEVICE_EVENTS)
    rt = _make_runtime(LANE_BATCH, CREATION_CAP)
    packer = _Packer(rt, events)
    out["ingress"] = packer.ingress

    t0 = time.perf_counter()
    packed = list(packer.iter_feeds())
    pack_s = time.perf_counter() - t0
    out["pack_s"] = round(pack_s, 3)

    # warmup / compile (persistent-cache hit when the compile phase ran)
    state, ys = _run_once(rt, rt.state, packed[0])
    _fence(state)

    # ---- throughput: fresh state (warmup replayed batch 0, which must not
    # double-count into matches/drops)
    state = rt.init_state()
    t0 = time.perf_counter()
    n_ev = 0
    for b in packed:
        state, ys = _run_once(rt, state, b)
        n_ev += b["count"]
    matches = _fence(state)         # real completion, not block_until_ready
    dt = time.perf_counter() - t0
    out["rate"] = n_ev / dt
    out["matches"] = matches
    out["drops"] = int(np.sum(jax.device_get(state["drops"])))
    print(f"# device: {n_ev} events in {dt:.3f}s -> {out['rate']:,.0f} "
          f"ev/s, {matches} matches, {out['drops']} dropped partials",
          file=sys.stderr)

    # ---- ingest/compute overlap: a packer thread builds batch N+1 while
    # the device steps batch N (the AsyncDeviceDriver's steady state, ring
    # depth 2). Dispatch is fire-and-forget — the only fence is the final
    # egress. Overlap efficiency = (pack + step) / overlapped wall; 1.0 =
    # serialized, 2.0 = two equal phases perfectly hidden.
    import queue as _queue
    import threading as _threading

    bq: "_queue.Queue" = _queue.Queue(maxsize=2)

    def _producer():
        for b in packer.iter_feeds():
            bq.put(b)
        bq.put(None)

    state3 = rt.init_state()
    t0 = time.perf_counter()
    prod = _threading.Thread(target=_producer, daemon=True)
    prod.start()
    n_ov = 0
    while True:
        b = bq.get()
        if b is None:
            break
        state3, ys = _run_once(rt, state3, b)
        n_ov += b["count"]
    _fence(state3)
    overlapped_s = time.perf_counter() - t0
    out["overlapped_rate"] = round(n_ov / overlapped_s)
    out["overlap_efficiency"] = round(
        (pack_s + dt) / overlapped_s if overlapped_s else 0.0, 3)
    # efficiency tops out at (pack+step)/max(pack,step) < 2 when the phases
    # imbalance (native pack is far cheaper than step); pack_hidden_frac
    # reports the overlap goal directly: 1.0 = the smaller phase is fully
    # hidden behind the larger, whatever their ratio
    hidden = pack_s + dt - overlapped_s
    out["pack_hidden_frac"] = round(
        max(0.0, min(1.0, hidden / min(pack_s, dt)))
        if min(pack_s, dt) > 0 else 0.0, 3)
    out["device_idle_frac"] = round(
        max(0.0, 1.0 - dt / overlapped_s) if overlapped_s else 0.0, 3)
    print(f"# overlap: pack={pack_s:.3f}s step={dt:.3f}s "
          f"overlapped={overlapped_s:.3f}s -> {out['overlapped_rate']:,} "
          f"ev/s end-to-end, efficiency={out['overlap_efficiency']:.2f}, "
          f"device idle {out['device_idle_frac']:.0%}", file=sys.stderr)
    return out


def _phase_latency() -> dict:
    """p50/p99 detection latency at an offered rate in the deadline-flush
    operating mode. The flush window comes from the AIMD controller in
    LATENCY mode (sized so fill-wait + observed-p99 step fits
    BENCH_LAT_BUDGET_MS — the @app:adaptive(latency.target.ms=...) knob);
    the closed-loop SLO search then walks offered rates upward and the
    "latency_mode" line records the chosen operating point."""
    import jax
    import jax.numpy as jnp

    out = {}
    window = LAT_WINDOW
    lrt = _make_runtime(LAT_LANE_BATCH, LAT_CREATION_CAP)
    lat_events = gen_events(min(DEVICE_EVENTS, LAT_WINDOW * 64))
    wpacked = _pack_windowed(lrt, lat_events, window)

    # warmup/compile the latency shapes (persistent-cache hit when the
    # compile phase ran), then measure steady-state capacity in this
    # operating mode over ALL windows (8-window samples were the r3
    # overload bug: capacity varies across the run)
    lstate, ys = _run_once(lrt, lrt.state, wpacked[0])
    _fence(lstate)

    state2 = lrt.init_state()
    t0 = time.perf_counter()
    for b in wpacked:
        state2, ys = _run_once(lrt, state2, b)
    _fence(state2)
    n_lat = sum(b["count"] for b in wpacked)
    wrate = n_lat / (time.perf_counter() - t0)

    adaptive = None
    if ADAPTIVE:
        # converge the window under the AIMD controller in LATENCY mode,
        # then repack with the chosen size. Lane shapes are static
        # (LAT_LANE_BATCH), so a different window only changes fill counts
        # — no recompilation. The convergence feed steps pre-packed windows
        # back-to-back, so the controller's own wall-clock arrival
        # estimator would read device capacity; pin it to the DESIGN
        # offered rate (the operating point the paced sweep serves) so the
        # fill-wait half of the prediction is honest — sizing stays
        # latency-targeted, not capacity-driven.
        from siddhi_tpu.flow.adaptive_batch import AdaptiveBatchController
        lam_design = min(OFFERED_EVPS, wrate * 0.75)
        _amax = window * 4
        ctrl = AdaptiveBatchController(
            min_batch=min(max(256, LAT_LANE_BATCH), _amax), max_batch=_amax,
            latency_target_ms=LAT_BUDGET_MS, initial=window, cooldown=1)
        for _ in range(6):
            w = ctrl.current
            apacked = _pack_windowed(lrt, lat_events[: w * 8], w)
            st = lrt.init_state()
            for b in apacked:
                t0 = time.perf_counter()
                st, ys = _run_once(lrt, st, b)
                int(jax.device_get(jnp.sum(ys["mask"])))
                ctrl.observe(int(b["count"]), time.perf_counter() - t0,
                             arrival_evps=lam_design)
            if ctrl.current == w:
                break               # operating point converged
        window = ctrl.current
        wpacked = _pack_windowed(lrt, lat_events, window)
        adaptive = ctrl.report()
        print(f"# latency-mode window: {window} events (budget "
              f"{LAT_BUDGET_MS}ms, design rate {lam_design:,.0f} ev/s, "
              f"observed step p99 {adaptive['p99_ms']}ms, flush deadline "
              f"{adaptive['flush_deadline_ms']}ms, static default "
              f"{LAT_WINDOW})", file=sys.stderr)

    def run_paced(lam):
        """Pace arrivals at lam ev/s; return (p50_ms, p99_ms, breakdown).

        The breakdown is the X-Ray latency attribution for this operating
        point: each window's detection latency cut into measured serial
        segments (fill-wait = window span / 2 per event under uniform
        arrival, device step, egress fence), recorded event-weighted into
        per-phase LogHistograms — so phase means SUM to the end-to-end
        mean by construction and the per-phase p99s answer "where did the
        p99 go". Every paced window is a deadline-flush window, so its
        fill-wait IS deadline-flush queueing (the r3 claim, now a field)."""
        from siddhi_tpu.core.metrics import LatencyTracker
        from siddhi_tpu.observability.phases import PhaseBreakdown
        bd = PhaseBreakdown(lambda ph: LatencyTracker(f"bench.{ph}"))
        state2 = lrt.init_state()
        base = time.perf_counter()
        envelopes = []      # (lo_latency, hi_latency, n_events) per batch
        for b in wpacked:
            release = base + (b["last_idx"] + 1) / lam
            while time.perf_counter() < release:
                pass
            t_s0 = time.perf_counter()
            state2, ys = _run_once(lrt, state2, b)
            t_s1 = time.perf_counter()
            # serving path: a device-side reduce -> ONE scalar d2h per
            # window; the full output slab transfers only when matches
            # exist (a bulk d2h of the whole output slab every window would
            # otherwise sit inside every latency sample)
            if int(jax.device_get(jnp.sum(ys["mask"]))):
                jax.device_get(ys)
            fin = time.perf_counter()
            # arrivals are linear in index and the window contiguous, so
            # the batch latencies span [fin-arr(newest), fin-arr(oldest)]
            # uniformly — envelope + population weight instead of
            # per-event floats
            envelopes.append((fin - (base + (b["last_idx"] + 1) / lam),
                              fin - (base + (b["first_idx"] + 1) / lam),
                              b["count"]))
            bd.record_batch(
                b["count"],
                fill_span_s=(b["last_idx"] - b["first_idx"]) / lam,
                step_s=t_s1 - t_s0, fence_s=fin - t_s1,
                cause="deadline")
        return (_envelope_percentile(envelopes, 0.50) * 1e3,
                _envelope_percentile(envelopes, 0.99) * 1e3,
                bd.report())

    # closed-loop SLO search (VERDICT r3 item 2): walk offered rates upward
    # and report the highest rate whose p99 meets the budget — never report
    # an overloaded measurement as THE number; the full curve ships in the
    # JSON
    curve = []
    breakdowns = {}
    best = None
    for frac in (0.3, 0.45, 0.6, 0.75, 0.9):
        lam = min(OFFERED_EVPS, wrate * frac)
        p50, p99, breakdown = run_paced(lam)
        curve.append({"offered_evps": round(lam), "p50_ms": round(p50, 2),
                      "p99_ms": round(p99, 2)})
        breakdowns[round(lam)] = breakdown
        print(f"# latency @ {lam:,.0f} ev/s offered: p50={p50:.2f}ms "
              f"p99={p99:.2f}ms (budget {LAT_BUDGET_MS}ms)",
              file=sys.stderr)
        if p99 <= LAT_BUDGET_MS:
            best = curve[-1]
        elif best is not None:
            break       # past the knee: higher rates only get worse
    if best is None:
        best = min(curve, key=lambda c: c["p99_ms"])

    # THE latency_breakdown line (X-Ray): the chosen operating point's
    # per-phase p50/p99/mean, the end-to-end reconciliation (phase means
    # sum to the e2e mean by construction), and the deadline-flush
    # queueing share as its own field — the r3 "p99 dominated by
    # deadline-flush queueing" claim, now measured instead of asserted
    breakdown = breakdowns[best["offered_evps"]]
    breakdown["envelope_p99_ms"] = best["p99_ms"]
    print(f"# latency-breakdown @ {best['offered_evps']:,} ev/s: "
          f"e2e mean {breakdown['end_to_end_mean_ms']:.2f}ms = "
          + " + ".join(f"{ph} {s['avg_ms']:.2f}ms"
                       for ph, s in breakdown["phases"].items())
          + f" (deadline-queueing share "
            f"{breakdown['deadline_flush_queueing_share']:.2f})",
          file=sys.stderr)

    out.update({
        "p50_ms": best["p50_ms"], "p99_ms": best["p99_ms"],
        "offered_evps": best["offered_evps"],
        "latency_curve": curve,
        "latency_breakdown": breakdown,
        "latency_budget_ms": LAT_BUDGET_MS,
        "latency_mode_capacity_evps": round(wrate),
    })
    if adaptive is not None:
        out["adaptive"] = adaptive
        # THE latency-mode line: offered rate, tail, and the window the
        # latency-target controller chose
        out["latency_mode"] = {
            "latency_target_ms": LAT_BUDGET_MS,
            "window": window,
            "flush_deadline_ms": adaptive["flush_deadline_ms"],
            "offered_evps": best["offered_evps"],
            "p50_ms": best["p50_ms"],
            "p99_ms": best["p99_ms"],
        }
        print(f"# latency-mode: target={LAT_BUDGET_MS}ms window={window} "
              f"offered={best['offered_evps']:,} ev/s "
              f"p50={best['p50_ms']}ms p99={best['p99_ms']}ms",
              file=sys.stderr)
    return out


def _phase_oracle() -> dict:
    """Device match count over the first ORACLE_EVENTS through a FRESH
    runtime; the parent compares against the host engine's count on the
    identical prefix (VERDICT r3 item 9)."""
    events = gen_events(ORACLE_EVENTS)
    ort = _make_runtime(LANE_BATCH, CREATION_CAP)
    for dev, v, ts in events:
        ort.send("S", [dev, v], ts)
    ort.flush()
    return {"oracle_matches": ort.match_count}


_DEVICE_PHASES = {
    "compile": _phase_compile,
    "throughput": _phase_throughput,
    "latency": _phase_latency,
    "oracle": _phase_oracle,
}


def child_device(phase: str = "all") -> None:
    """One device-bench phase per process (the parent sequences them under
    per-phase deadlines); ``all`` keeps the monolithic single-process shape
    for direct invocation."""
    _phase_hook(phase)
    import jax
    from siddhi_tpu.tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = {}
    names = list(_DEVICE_PHASES) if phase == "all" else [phase]
    for name in names:
        out.update(_DEVICE_PHASES[name]())
    out["fence"] = "device_get"
    out["platform"] = jax.default_backend()
    print(json.dumps(out))


def child_host() -> None:
    """Host benchmark: BOTH host execution tiers as separate lines.

    1. the scalar per-event interpreter (the historical baseline — the
       vs_baseline denominator and BASELINE.json's ``host_baseline`` seed);
    2. the columnar micro-batch engine (@app:host_batch → the vectorized
       numpy fast path shared with the device compiler), fed in chunks via
       ``InputHandler.send_rows`` — the micro-batches the flow layer would
       assemble.

    Both engines process the identical ORACLE_EVENTS prefix; their match
    counts must agree (host-side parity cross-check, mirroring the
    device-vs-host oracle)."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    # identical prefix to the device stream: the seeded RNG is consumed
    # strictly sequentially, so generating only the needed count suffices
    events = gen_events(max(BASELINE_EVENTS, ORACLE_EVENTS))

    # ---- tier 3: scalar interpreter --------------------------------------
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(make_app(), playback=True)
    if BENCH_METRICS:
        from siddhi_tpu.core.metrics import Level
        rt.set_statistics_level(Level.BASIC)
    n_matches = 0

    def on_out(evs):
        nonlocal n_matches
        n_matches += len(evs)

    rt.add_callback("Alerts", StreamCallback(on_out))
    rt.start()
    ih = rt.input_handler("S")
    t0 = time.perf_counter()
    for dev, v, ts in events[:BASELINE_EVENTS]:
        ih.send([dev, v], timestamp=ts)
    dt = time.perf_counter() - t0
    rate = BASELINE_EVENTS / dt
    # continue the identical prefix to the oracle horizon (not timed)
    for dev, v, ts in events[BASELINE_EVENTS:ORACLE_EVENTS]:
        ih.send([dev, v], timestamp=ts)
    child_out = {"rate": rate, "oracle_matches": n_matches}
    if BENCH_METRICS:
        # final statistics snapshot (percentile latencies, throughput,
        # flow/resilience gauges) rides alongside the timings
        child_out["metrics"] = rt.ctx.statistics_manager.report()
    m.shutdown()
    print(f"# interpreter: {BASELINE_EVENTS} events in {dt:.3f}s -> "
          f"{rate:,.0f} ev/s; oracle matches over {ORACLE_EVENTS}: "
          f"{n_matches}", file=sys.stderr)

    # ---- tier 2: columnar host engine ------------------------------------
    try:
        mc = SiddhiManager()
        crt = mc.create_siddhi_app_runtime(
            f"@app:host_batch(batch='{HOST_CHUNK}', lanes='{HOST_LANES}')\n"
            + make_app(), playback=True)
        c_matches = 0

        def on_cout(evs):
            nonlocal c_matches
            c_matches += len(evs)

        crt.add_callback("Alerts", StreamCallback(on_cout))
        crt.start()
        cih = crt.input_handler("S")
        engine = "columnar" if crt.host_bridges else "scalar-fallback"
        rows = [[dev, v] for dev, v, _ in events[:ORACLE_EVENTS]]
        tss = [ts for _, _, ts in events[:ORACLE_EVENTS]]
        # warm the numpy kernels / dictionary encode on a SCRATCH runtime so
        # the measured run starts from steady state without polluting the
        # oracle app's pattern state
        wm = SiddhiManager()
        wrt = wm.create_siddhi_app_runtime(
            f"@app:host_batch(batch='{HOST_CHUNK}', lanes='{HOST_LANES}')\n"
            + make_app(), playback=True)
        wrt.start()
        wrt.input_handler("S").send_rows(
            [list(r) for r in rows[:HOST_CHUNK]], tss[:HOST_CHUNK])
        wm.shutdown()
        t0 = time.perf_counter()
        for i in range(0, ORACLE_EVENTS, HOST_CHUNK):
            cih.send_rows(rows[i:i + HOST_CHUNK], tss[i:i + HOST_CHUNK])
        crt.flush_host()            # surface the final partial micro-batch
        cdt = time.perf_counter() - t0
        crate = ORACLE_EVENTS / cdt
        mc.shutdown()
        child_out.update({
            "host_batch_rate": crate,
            "host_batch_oracle_matches": c_matches,
            "host_engine": engine,
            "host_batch_chunk": HOST_CHUNK,
            "host_batch_lanes": HOST_LANES,
        })
        print(f"# host_batch ({engine}): {ORACLE_EVENTS} events in "
              f"{cdt:.3f}s -> {crate:,.0f} ev/s; oracle matches: "
              f"{c_matches}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — the scalar line already secured
        # a usable result; a fast-path failure is reported, not fatal
        child_out["host_batch_error"] = str(e)
        print(f"# host_batch failed: {e}", file=sys.stderr)
    print(json.dumps(child_out))


def _edge_csv(events) -> bytes:
    """The transport payload a socket/file would deliver (building it is
    data generation, not ingest — untimed)."""
    return "".join(f"{dev},{v},{ts}\n" for dev, v, ts in events).encode()


def _edge_rule_app(name: str, batch: int, topic: str = "edge-warm") -> str:
    # rows-capable sink on the output stream: the measured path covers the
    # FULL edge — bytes → columns → engine → columnar sink publish
    return f"""
@app(name='{name}')
@app:host_batch(batch='{batch}', lanes='8')
define stream S (dev string, v double);
@sink(type='inMemory', topic='{topic}', @map(type='passThrough'))
define stream Alerts (dev string, v double);
from S[v > 90.0] select dev, v insert into Alerts;
"""


def _edge_pattern_app(name: str, workers: int) -> str:
    states = " -> ".join(
        f"e{i}=S[v > e{i-1}.v]" if i > 1 else "e1=S[v > 90.0]"
        for i in range(1, N_STATES + 1))
    sel = ", ".join(f"e{i}.v as v{i}" for i in range(1, N_STATES + 1))
    return f"""
@app(name='{name}')
@app:host_batch(batch='{EDGE_PAR_BATCH}', lanes='{EDGE_PAR_LANES}',
                workers='{workers}')
define stream S (dev string, v double);
partition with (dev of S)
begin
from every {states} within 4000
select {sel} insert into Alerts;
end;
"""


def _edge_feed(parser, csv: bytes, ih, flush) -> float:
    """Stream the payload in transport-sized reads through parse →
    send_columns; returns wall seconds."""
    pos, total = 0, len(csv)
    t0 = time.perf_counter()
    while pos < total:
        end = csv.rfind(b"\n", 0, pos + EDGE_CHUNK_BYTES) + 1
        if end <= pos:
            end = total
        for ch in parser.parse(csv[pos:end]):
            ih.send_columns(ch.cols, ch.ts, ch.count)
        pos = end
    flush()
    return time.perf_counter() - t0


def _thread_ceiling() -> float:
    """What THIS container's cores/bandwidth allow: 2-thread speedup on a
    representative memory-bound boolean-grid op mix (the parallel tier
    cannot beat this no matter how it shards)."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor

    def work(seed):
        rng = np.random.default_rng(seed)
        a = rng.random((2048, 400))
        b = rng.random((1, 400))
        t = rng.random(2048)[:, None]
        s = 0.0
        for _ in range(20):
            s += ((a > b) & (t < 0.5)).any(axis=0).sum()
        return s

    t0 = time.perf_counter()
    for i in range(4):
        work(i)
    seq = time.perf_counter() - t0
    with ThreadPoolExecutor(2) as ex:
        t0 = time.perf_counter()
        list(ex.map(work, range(4)))
        par = time.perf_counter() - t0
    return seq / par if par else 0.0


def child_edge() -> None:
    """Zero-object edge line: host bytes-in → rows-out.

    1. **edge rule line** — EDGE_EVENTS rows of raw CSV transport bytes
       parsed into columns (native C++ ingress when available) and fed via
       ``send_columns`` through a columnar rule query into a rows-capable
       in-memory sink: rows/s end to end, parse share, and an allocation
       assertion that ZERO ``Event``/``StreamEvent`` objects were built on
       the measured path (instrumented constructors stay armed during the
       timed run — they cost nothing when never called);
    2. **parallel tier line** — the bench pattern corpus through
       ``@app:host_batch(workers=W)`` for W ∈ {1,2,4}: rates, speedups and
       a zero-mismatch parity pin across worker counts, plus this
       container's measured thread-scaling ceiling for context.
    """
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.columns import CsvColumnParser, RowsChunk
    from siddhi_tpu.core.event import Event, StreamEvent
    from siddhi_tpu.core.io import InMemoryBroker

    out = {"events": EDGE_EVENTS, "chunk_bytes": EDGE_CHUNK_BYTES,
           "batch": EDGE_BATCH, "cpus": os.cpu_count()}
    events = gen_events(EDGE_EVENTS)
    csv = _edge_csv(events)
    out["bytes_in"] = len(csv)

    # arm the allocation counters for the WHOLE edge run: the zero-object
    # claim is then an assertion over the measured path itself
    counts = {"se": 0, "ev": 0}
    se_init, ev_init = StreamEvent.__init__, Event.__init__

    def _se(self, *a, **k):
        counts["se"] += 1
        se_init(self, *a, **k)

    def _ev(self, *a, **k):
        counts["ev"] += 1
        ev_init(self, *a, **k)

    try:
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(
            _edge_rule_app("edge-warm", EDGE_BATCH), playback=True)
        rt.start()
        wih = rt.input_handler("S")
        defn = rt.ctx.stream_junctions["S"].definition
        wparser = CsvColumnParser(defn, ts_last=True, capacity=EDGE_BATCH)
        out["ingress"] = wparser.ingress
        # warm numpy kernels + dictionaries on a scratch runtime
        _edge_feed(wparser, csv[:EDGE_CHUNK_BYTES], wih, rt.flush_host)
        m.shutdown()

        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(
            _edge_rule_app("edge", EDGE_BATCH, topic="edge-out"),
            playback=True)
        sink_rows = [0]

        def on_pub(payload):
            sink_rows[0] += payload.count if isinstance(payload, RowsChunk) \
                else 1

        unsub = InMemoryBroker.subscribe("edge-out", on_pub)
        rt.start()
        parser = CsvColumnParser(defn, ts_last=True, capacity=EDGE_BATCH)
        ih = rt.input_handler("S")
        StreamEvent.__init__, Event.__init__ = _se, _ev
        dt = _edge_feed(parser, csv, ih, rt.flush_host)
        StreamEvent.__init__, Event.__init__ = se_init, ev_init
        unsub()
        m.shutdown()
        out.update({
            "rows_per_s": round(EDGE_EVENTS / dt),
            "seconds": round(dt, 3),
            "bytes_per_s": round(len(csv) / dt),
            "parse_share": round(parser.parse_seconds / dt, 3),
            "parse_rows_per_s": round(parser.rows_per_s),
            "parse_errors": parser.parse_errors,
            "out_rows": sink_rows[0],
            "objects_per_row": (counts["se"] + counts["ev"]) / EDGE_EVENTS,
            "objects": dict(counts),
        })
        print(f"# edge ({out['ingress']}): {EDGE_EVENTS} rows in {dt:.3f}s "
              f"-> {out['rows_per_s']:,} rows/s (parse share "
              f"{out['parse_share']:.2f}), {sink_rows[0]} sink rows, "
              f"objects/row={out['objects_per_row']}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — parallel line still valuable
        StreamEvent.__init__, Event.__init__ = se_init, ev_init
        out["error"] = str(e)
        print(f"# edge rule line failed: {e}", file=sys.stderr)

    # ---- parallel columnar host tier: workers ∈ {1,2,4} ------------------
    try:
        par_csv = _edge_csv(gen_events(EDGE_PAR_EVENTS))
        workers_out = {}
        matches = {}
        # interleaved best-of-3 (the X-Ray overhead pin's pattern): the
        # shared container's cores are noisy, and back-to-back per-W
        # sampling turns a quiet window into a fake speedup (or slowdown)
        best: dict = {}
        for rep in range(3):
            for W in (1, 2, 4):
                m = SiddhiManager()
                rt = m.create_siddhi_app_runtime(
                    _edge_pattern_app(f"edge-par-{W}-{rep}", W),
                    playback=True)
                rt.start()
                defn = rt.ctx.stream_junctions["S"].definition
                p = CsvColumnParser(defn, ts_last=True,
                                    capacity=EDGE_PAR_BATCH)
                dt = _edge_feed(p, par_csv, rt.input_handler("S"),
                                rt.flush_host)
                mcount = rt.host_bridges[0].runtime.prt.match_count
                m.shutdown()
                if W in matches and matches[W] != mcount:
                    matches[W] = -1         # intra-W nondeterminism: loud
                else:
                    matches[W] = mcount
                if rep:                     # rep 0 is the warm pass
                    best[W] = min(best.get(W, dt), dt)
        for W in (1, 2, 4):
            workers_out[str(W)] = round(EDGE_PAR_EVENTS / best[W])
            print(f"# edge parallel tier workers={W}: "
                  f"{workers_out[str(W)]:,} ev/s, matches={matches[W]}",
                  file=sys.stderr)
        r1 = workers_out["1"]
        out["workers"] = workers_out
        out["workers_speedup_2"] = round(workers_out["2"] / r1, 3) if r1 \
            else 0.0
        out["workers_speedup_4"] = round(workers_out["4"] / r1, 3) if r1 \
            else 0.0
        out["workers_parity_ok"] = matches[1] == matches[2] == matches[4]
        out["workers_matches"] = matches[1]
        out["thread_ceiling_2"] = round(_thread_ceiling(), 3)
        out["workers_events"] = EDGE_PAR_EVENTS
        out["workers_note"] = (
            "2-cpu container: the lane-sharded step only beats sequential "
            "when per-lane grids are large (measured 1.5x at "
            "batch=131072, where absolute rate is lower); at the optimal "
            "batch the step is small-op/GIL-bound and threads wash out — "
            "the >=2x target needs >=4 real cores")
        print(f"# edge parallel: 2w={out['workers_speedup_2']}x "
              f"4w={out['workers_speedup_4']}x (container 2-thread numpy "
              f"ceiling {out['thread_ceiling_2']}x over {out['cpus']} "
              f"cpus), parity_ok={out['workers_parity_ok']}",
              file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — rule line already secured
        out["workers_error"] = str(e)
        print(f"# edge parallel tier failed: {e}", file=sys.stderr)
    print(json.dumps(out))


def _tenant_rule_app(i: int, ann: str) -> str:
    """Tenant i's alert rule: the multi-tenant serving template — same shape
    for every tenant, per-tenant constants (threshold / device / scale)."""
    return f"""
@app(name='tenant-{i}')
{ann}define stream S (dev string, v double);
@info(name='rule')
from S[v > {85.0 + (i % 8) * 0.25} and dev == 'dev{i % 32}']
select dev, v, v * {1.0 + i * 0.001} as score insert into Alerts;
"""


def _tenant_pattern_app(i: int, ann: str) -> str:
    """Tenant i's copy of the bench pattern (3-state rising chain, 64-way
    partitioned) — the stateful fleet line: shared blocked-NFA plan, sliced
    tenant lanes."""
    return f"""
@app(name='ptenant-{i}')
{ann}define stream S (dev string, v double);
partition with (dev of S)
begin
from every e1=S[v > {90.0 + (i % 8) * 0.25}] -> e2=S[v > e1.v]
    -> e3=S[v > e2.v] within {4000 + 250 * (i % 4)}
select e1.v as v1, e2.v as v2, e3.v as v3 insert into Alerts;
end;
"""


def _run_tenant_fleet(make_tenant, ann, n_feed: int, chunk: int,
                      tenants: int):
    """K tenant apps over the shared feed, per-tenant chunk deliveries
    through the zero-wrap rows ingress (``send_rows`` → ``deliver_rows``
    → fleet stagers: no per-event StreamEvent wrapping on the fleet/solo
    columnar tiers; the scalar control run gets per-tenant row copies —
    interpreter events alias row lists, so sharing would be unsafe there).
    The columnar ``send_columns`` ingress also works here (pinned by
    tests/test_edge_rows.py) but measures ~1.7x SLOWER at this chunk size:
    16-row numpy chunks pay fixed per-chunk array overhead that plain list
    staging doesn't — columns win from ~hundreds of rows per chunk, which
    is the columnar SOURCE regime (see the edge line), not the
    multiplexed-tenant regime this scenario models.
    Returns (aggregate ev/s, per-tenant match counts, compiles, steps)."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    feed = gen_events(n_feed)
    rows = [[dev, v] for dev, v, _ in feed]
    tss = [ts for _, _, ts in feed]
    chunks = [(rows[s:s + chunk], tss[s:s + chunk])
              for s in range(0, n_feed, chunk)]
    m = SiddhiManager()
    apps, counts = [], [0] * tenants
    for i in range(tenants):
        rt = m.create_siddhi_app_runtime(make_tenant(i, ann), playback=True)
        rt.add_callback("Alerts", StreamCallback(
            lambda evs, i=i: counts.__setitem__(i, counts[i] + len(evs))))
        rt.start()
        apps.append(rt)
    ihs = [rt.input_handler("S") for rt in apps]
    warm = max(1, len(chunks) // 20)
    for c, t in chunks[:warm]:
        for ih in ihs:
            ih.send_rows([list(r) for r in c], list(t))
    for rt in apps:
        rt.flush_host()
    t0 = time.perf_counter()
    for c, t in chunks[warm:]:
        for ih in ihs:
            ih.send_rows([list(r) for r in c], list(t))
    for rt in apps:
        rt.flush_host()
    dt = time.perf_counter() - t0
    total = tenants * (n_feed - warm * chunk)
    guard = {}
    if any(rt.fleet_bridges for rt in apps):
        fstats = m.fleet.stats()
        compiles = fstats["cache"]["misses"]
        steps = sum(g["steps"] for g in fstats["groups"].values())
        lanes = [b.member.lane for rt in apps for b in rt.fleet_bridges
                 if b.member.lane is not None]
        if lanes:
            guard = {"ejections": sum(l.ejections for l in lanes),
                     "readmissions": sum(l.readmissions for l in lanes),
                     "containments": sum(
                         g.get("guard", {}).get("containments", 0)
                         for g in fstats["groups"].values())}
    else:
        # solo: every app compiled its own plan(s) and stepped its own
        # bridges (the per-APP dedupe cannot cross tenants)
        compiles = sum(len(rt.host_bridges) for rt in apps)
        steps = sum(b.batches for rt in apps for b in rt.host_bridges)
    engaged = sum(len(rt.fleet_bridges) for rt in apps) or \
        sum(len(rt.host_bridges) for rt in apps)
    m.shutdown()
    return {"rate": total / dt, "events": total, "seconds": dt,
            "matches": list(counts), "compiles": compiles,
            "steps": steps, "steps_per_s": steps / dt if dt else 0.0,
            "engaged": engaged, **guard}


def child_fleet() -> None:
    """Multi-tenant fleet scenario: K copies of the tenant rule (and of the
    bench pattern) under distinct apps — fleet (@app:fleet shared plans +
    cross-app lanes) vs solo (@app:host_batch per-app columnar), identical
    feed, per-tenant oracle parity."""
    fleet_ann = f"@app:fleet(batch='{FLEET_BATCH}', lanes='{HOST_LANES}')\n"
    solo_ann = f"@app:host_batch(batch='{FLEET_BATCH}', " \
               f"lanes='{HOST_LANES}')\n"
    # throwaway warm pass (numpy kernels, dictionary encode, parse)
    _run_tenant_fleet(_tenant_rule_app, fleet_ann,
                      max(TENANT_CHUNK * 40, 1280), TENANT_CHUNK, TENANTS)
    solo = _run_tenant_fleet(_tenant_rule_app, solo_ann, TENANT_FEED,
                             TENANT_CHUNK, TENANTS)
    fleet = _run_tenant_fleet(_tenant_rule_app, fleet_ann, TENANT_FEED,
                              TENANT_CHUNK, TENANTS)
    scalar = _run_tenant_fleet(_tenant_rule_app, "", TENANT_FEED,
                               TENANT_CHUNK, TENANTS)
    out = {
        "tenants": TENANTS,
        "tenant_chunk": TENANT_CHUNK,
        "feed_events": TENANT_FEED,
        "fleet_evps": round(fleet["rate"]),
        "solo_evps": round(solo["rate"]),
        "scalar_evps": round(scalar["rate"]),
        "fleet_vs_solo": fleet["rate"] / solo["rate"] if solo["rate"] else 0,
        "fleet_vs_scalar": fleet["rate"] / scalar["rate"]
        if scalar["rate"] else 0,
        "fleet_compiles": fleet["compiles"],
        "solo_compiles": solo["compiles"],
        "fleet_steps_per_s": round(fleet["steps_per_s"], 1),
        "solo_steps_per_s": round(solo["steps_per_s"], 1),
        "fleet_engaged": fleet["engaged"],
        "oracle_ok": fleet["matches"] == solo["matches"] == scalar["matches"],
        "matches_total": sum(fleet["matches"]),
    }
    print(f"# fleet rule: {out['fleet_evps']:,} ev/s vs solo "
          f"{out['solo_evps']:,} ({out['fleet_vs_solo']:.2f}x) vs scalar "
          f"{out['scalar_evps']:,} ({out['fleet_vs_scalar']:.2f}x); "
          f"compiles fleet={out['fleet_compiles']} "
          f"solo={out['solo_compiles']}; oracle_ok={out['oracle_ok']}",
          file=sys.stderr)
    # fault-mode line (FleetGuard containment): tenant 0 faults at the
    # chaos fleet site — ejected to solo, later re-admitted — and the
    # innocent tenants' aggregate throughput must stay within ~10% of a
    # back-to-back no-fault run of the SAME config (small guard batch so
    # containment actually engages over the reduced feed; the 64-tenant
    # p=0.05 correctness soak lives in tests/test_fleet_guard.py).
    # BENCH_FLEET_FAULT=0 skips.
    if os.environ.get("BENCH_FLEET_FAULT", "1") == "1" and TENANTS > 1:
        guard_batch = min(FLEET_BATCH, 2048)
        guard_ann = f"@app:fleet(batch='{guard_batch}', " \
                    f"lanes='{HOST_LANES}', guard.cooldown.ms='20', " \
                    f"guard.readmit.batches='2')\n"
        chaos_ann = "@app:chaos(seed='29', fleet.fault.p='0.2')\n"

        def make_faulted(i, ann):
            return _tenant_rule_app(
                i, ann + (chaos_ann if i == 0 else ""))

        base = _run_tenant_fleet(_tenant_rule_app, guard_ann, TENANT_FEED,
                                 TENANT_CHUNK, TENANTS)
        fault = _run_tenant_fleet(make_faulted, guard_ann, TENANT_FEED,
                                  TENANT_CHUNK, TENANTS)
        innocents_ok = fault["matches"][1:] == base["matches"][1:]
        # per-tenant offered load is identical, so the innocent tenants'
        # throughput ratio IS the aggregate wall ratio
        ratio = fault["rate"] / base["rate"] if base["rate"] else 0.0
        out.update({
            "fault_evps": round(fault["rate"]),
            "fault_baseline_evps": round(base["rate"]),
            "fault_innocent_ratio": ratio,
            "fault_ejections": fault.get("ejections", 0),
            "fault_readmissions": fault.get("readmissions", 0),
            "fault_containments": fault.get("containments", 0),
            "fault_innocents_oracle_ok": innocents_ok,
        })
        print(f"# fleet fault (p=0.2 tenant 0): {out['fault_evps']:,} "
              f"ev/s = {ratio:.2f}x no-fault; ejections="
              f"{out['fault_ejections']} readmissions="
              f"{out['fault_readmissions']} containments="
              f"{out['fault_containments']}; innocents_ok={innocents_ok}",
              file=sys.stderr)
    # stateful line: the bench pattern (64-way partitioned rising chain) as
    # K tenant copies — shared blocked-NFA plan, sliced tenant lanes
    # (BENCH_FLEET_PATTERN_FEED=0 skips it — the CI guard's fast path)
    if FLEET_PATTERN_FEED <= 0:
        print(json.dumps(out))
        return
    try:
        psolo = _run_tenant_fleet(_tenant_pattern_app, solo_ann,
                                  FLEET_PATTERN_FEED, TENANT_CHUNK, TENANTS)
        pfleet = _run_tenant_fleet(_tenant_pattern_app, fleet_ann,
                                   FLEET_PATTERN_FEED, TENANT_CHUNK, TENANTS)
        out.update({
            "pattern_fleet_evps": round(pfleet["rate"]),
            "pattern_solo_evps": round(psolo["rate"]),
            "pattern_fleet_vs_solo": pfleet["rate"] / psolo["rate"]
            if psolo["rate"] else 0,
            "pattern_fleet_compiles": pfleet["compiles"],
            "pattern_solo_compiles": psolo["compiles"],
            "pattern_oracle_ok": pfleet["matches"] == psolo["matches"],
        })
        print(f"# fleet pattern: {out['pattern_fleet_evps']:,} ev/s vs solo "
              f"{out['pattern_solo_evps']:,} "
              f"({out['pattern_fleet_vs_solo']:.2f}x); compiles "
              f"fleet={out['pattern_fleet_compiles']} "
              f"solo={out['pattern_solo_compiles']}; "
              f"oracle_ok={out['pattern_oracle_ok']}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — rule line already secured
        out["pattern_error"] = str(e)
        print(f"# fleet pattern failed: {e}", file=sys.stderr)
    print(json.dumps(out))


def child_slo() -> None:
    """SLO-autopilot noisy-neighbour storm: K fleet tenants of the rule
    shape with declared SLO classes (premium / standard / besteffort), the
    last best-effort tenant bursting at SLO_BURST× its share over a
    CPU-bound multiplexed feed. Phase 1 lets the closed loop converge
    (shed the neighbour, shrink the window); phase 2 measures the settled
    per-event p99 against the declared premium budget. Evidence out:
    premium p99 vs budget, decisions taken (with the flight-recorder
    trail), premium sheds (must be 0) vs best-effort sheds (absorb)."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    def klass(i: int) -> str:
        if i < SLO_TENANTS // 4:
            return "premium"
        if i >= SLO_TENANTS - max(2, SLO_TENANTS // 4):
            return "besteffort"
        return "standard"

    def ann(i: int) -> str:
        k = klass(i)
        budget = f", slo.p99.ms='{SLO_BUDGET_MS}'" if k == "premium" else ""
        return (f"@app:fleet(batch='{SLO_BATCH}', lanes='{HOST_LANES}', "
                f"slo.class='{k}'{budget}, slo.interval.ms='2', "
                f"slo.cooldown.ms='100', slo.window.min='256')\n")

    feed = gen_events(SLO_FEED)
    rows = [[dev, v] for dev, v, _ in feed]
    tss = [ts for _, _, ts in feed]
    m = SiddhiManager()
    apps, counts = [], [0] * SLO_TENANTS
    for i in range(SLO_TENANTS):
        rt = m.create_siddhi_app_runtime(
            _tenant_rule_app(i, ann(i)), playback=True)
        rt.add_callback("Alerts", StreamCallback(
            lambda evs, i=i: counts.__setitem__(i, counts[i] + len(evs))))
        rt.start()
        apps.append(rt)
    ihs = [rt.input_handler("S") for rt in apps]
    burster = SLO_TENANTS - 1           # a best-effort lane by klass()
    group = apps[0].fleet_bridges[0].member.group
    ctrl = group.slo
    window_initial = group.effective_window()

    def storm(lo: int, hi: int) -> None:
        for s in range(lo, hi, SLO_CHUNK):
            c = rows[s:s + SLO_CHUNK]
            t = tss[s:s + SLO_CHUNK]
            for j, ih in enumerate(ihs):
                reps = SLO_BURST if j == burster else 1
                for _ in range(reps):
                    ih.send_rows([list(r) for r in c], list(t))

    t0 = time.perf_counter()
    split = int(SLO_FEED * 0.4)
    storm(0, split)                     # phase 1: the loop converges
    # convergence wait: keep the storm blowing (cycling phase-1 rows)
    # until the controller has been quiet for a stretch — the settled
    # measurement must judge the FINAL operating point, not the ladder's
    # descent. Bounded: at most one extra SLO_FEED of replayed traffic.
    last_d, t_stable = ctrl.decisions, time.perf_counter()
    extra = 0
    while time.perf_counter() - t_stable < 0.4 and extra < SLO_FEED:
        lo = extra % max(split - SLO_CHUNK, 1)
        storm(lo, lo + SLO_CHUNK)
        extra += SLO_CHUNK
        if ctrl.decisions != last_d:
            last_d, t_stable = ctrl.decisions, time.perf_counter()
    settled_chk = {p: h.checkpoint()
                   for p, h in ctrl.evidence.hist.items()}
    storm(split, SLO_FEED)              # phase 2: settled measurement
    for rt in apps:
        rt.flush_host()
    # the converged line: evidence since the controller's LAST
    # intervention (advance() runs at each decision, so the un-consumed
    # window IS the quiet stretch at the final operating point). A shared
    # CI box can stall the offered load mid-phase and transiently violate
    # — the controller reacts, and what counts is where the loop SETTLES.
    quiet = ctrl.evidence.window()
    ctrl.maybe_evaluate(force=True)
    wall = time.perf_counter() - t0

    settled = {p: ctrl.evidence.hist[p].since(settled_chk[p])
               for p in ctrl.evidence.hist}
    # too-thin quiet window (a decision fired near the very end): judge
    # the whole settled phase instead of a handful of events
    e2e = quiet["end_to_end"] \
        if quiet["end_to_end"]["count"] >= 4096 else settled["end_to_end"]
    # offered includes the convergence-wait replays — `wall` timed them,
    # so leaving them out would understate evps
    offered = (SLO_FEED + extra) * (SLO_TENANTS - 1 + SLO_BURST)
    lanes = {rt.fleet_bridges[0].member.tenant:
             rt.fleet_bridges[0].member.lane for rt in apps}
    prem = [f"tenant-{i}" for i in range(SLO_TENANTS)
            if klass(i) == "premium"]
    beff = [f"tenant-{i}" for i in range(SLO_TENANTS)
            if klass(i) == "besteffort"]
    premium_sheds = sum(lanes[t].shed for t in prem if lanes[t])
    besteffort_sheds = sum(lanes[t].shed for t in beff if lanes[t])
    trail = apps[0].ctx.flight.export(category="slo")
    decision_kinds = [e["kind"][len("decision:"):] for e in trail
                     if e["kind"].startswith("decision:")]
    out = {
        "tenants": SLO_TENANTS,
        "premium": len(prem),
        "besteffort": len(beff),
        "burst_factor": SLO_BURST,
        "budget_ms": SLO_BUDGET_MS,
        "offered_events": offered,
        "processed_events": group.events_in,
        "evps": round(offered / wall) if wall else 0,
        "premium_p99_ms": round(e2e["p99"] * 1e3, 3),
        "premium_p50_ms": round(settled["end_to_end"]["p50"] * 1e3, 3),
        "phase2_p99_ms": round(settled["end_to_end"]["p99"] * 1e3, 3),
        "quiet_window_events": quiet["end_to_end"]["count"],
        "settled_fill_wait_p99_ms":
            round(settled["fill_wait"]["p99"] * 1e3, 3),
        "settled_step_p99_ms": round(settled["step"]["p99"] * 1e3, 3),
        "in_budget": e2e["p99"] * 1e3 <= SLO_BUDGET_MS,
        "decisions": ctrl.decisions,
        "decision_kinds": decision_kinds,
        "premium_sheds": premium_sheds,
        "besteffort_sheds": besteffort_sheds,
        "window_initial": window_initial,
        "window_final": group.effective_window(),
        "matches_total": sum(counts),
    }
    print(f"# slo storm: premium p99 {out['premium_p99_ms']}ms vs budget "
          f"{SLO_BUDGET_MS}ms (in_budget={out['in_budget']}); decisions="
          f"{out['decisions']} {decision_kinds[:8]}; sheds premium="
          f"{premium_sheds} besteffort={besteffort_sheds:,}; window "
          f"{window_initial}->{out['window_final']}", file=sys.stderr)
    m.shutdown()
    print(json.dumps(out))


def _mesh_shape_app(i: int, shape: int, ann: str) -> str:
    """Tenant i of structural shape ``shape``: filter conjunct count and
    select-list length are STRUCTURAL (different fleet fingerprints), the
    thresholds stay per-tenant constants (hoisted to params — tenants of
    one shape still share one compiled program)."""
    terms = " and ".join(
        [f"v > {80.0 + i % 8}"] + [f"v < {200.0 + j}"
                                   for j in range(shape % 4)])
    sel = ", ".join(["dev", "v"] + [f"v * {1.5 + j} as x{j}"
                                    for j in range(shape // 4 + 1)])
    return (f"@app(name='mtenant-{i}')\n{ann}"
            f"define stream S (dev string, v double);\n"
            f"@info(name='rule')\n"
            f"from S[{terms}] select {sel} insert into Alerts;\n")


def _mesh_kleene_app(i: int, ann: str) -> str:
    """Tenant i's Kleene anomaly rule: the BASELINE.json config-#5 family
    (rising chain over the 64-way partitioned synthetic IoT stream) sized
    for the CPU fleet tier — the scaling line's workload."""
    return (f"@app(name='kleene-{i}')\n{ann}"
            f"define stream S (dev string, v double);\n"
            f"partition with (dev of S)\nbegin\n"
            f"from every e1=S[v > {90.0 + (i % 8) * 0.25}] -> e2=S[v > e1.v]"
            f" -> e3=S[v > e2.v] within 4000\n"
            f"select e1.v as v1, e2.v as v2, e3.v as v3 insert into Alerts;"
            f"\nend;\n")


def _mesh_feed_all(fabric, tenant_ids, rows, tss, chunk, threads=None):
    """Per-host feeder threads drive every tenant's chunks through the
    fabric ingress (each host's tenants fed from one thread — the
    per-host DCN-ingest model). Returns wall seconds."""
    import threading as _th
    by_host = {}
    for t in tenant_ids:
        by_host.setdefault(fabric.tenants[t].host, []).append(t)

    def feed(tids):
        for s in range(0, len(rows), chunk):
            c = rows[s:s + chunk]
            t = tss[s:s + chunk]
            for tid in tids:
                fabric.send(tid, "S", c, t)

    t0 = time.perf_counter()
    ths = [_th.Thread(target=feed, args=(tids,))
           for tids in by_host.values()]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    fabric.flush()
    return time.perf_counter() - t0


def child_mesh() -> None:
    """Mesh-fabric evidence: placement quality at population scale,
    ev/s-per-chip scaling curves, live migration + elasticity under
    sustained ingest — the MULTICHIP_r06 line (ROADMAP item 3)."""
    import tempfile

    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.mesh import MeshConfig, MeshFabric

    fleet_ann = f"@app:fleet(batch='{FLEET_BATCH}', lanes='{HOST_LANES}')\n"
    out = {"hosts": MESH_HOSTS, "devices": None}
    try:
        import jax
        out["devices"] = len(jax.devices())
        out["platform"] = jax.devices()[0].platform
    except Exception as e:  # noqa: BLE001 — device binding is metadata
        out["device_probe_error"] = str(e)

    # -- 1) placement quality: locality vs random at population scale ------
    T, H = MESH_PLACE_TENANTS, MESH_HOSTS
    cap = (T + H - 1) // H            # equal fill: policies differ ONLY in
    # which tenants co-locate, not how many land per host
    feed = gen_events(MESH_PLACE_FEED)
    prows = [[dev, v] for dev, v, _ in feed]
    ptss = [ts for _, _, ts in feed]
    placement = {}
    for policy in ("locality", "random"):
        t0 = time.perf_counter()
        fab = MeshFabric(H, tempfile.mkdtemp(prefix=f"mesh-{policy}-"),
                         MeshConfig(capacity_per_host=cap, policy=policy))
        fab.add_tenants([
            _mesh_shape_app(i, i % MESH_SHAPES, fleet_ann)
            for i in range(T)])
        deploy_s = time.perf_counter() - t0
        wall = _mesh_feed_all(fab, [f"mtenant-{i}" for i in range(T)],
                              prows, ptss, MESH_CHUNK)
        ev = fab.evidence()
        compiles = [e["compiled_programs"] for e in ev.values()]
        lanes = [e["lanes_per_step"] for e in ev.values()
                 if e["lanes_per_step"]]
        placement[policy] = {
            "tenants_per_host": [e["tenants"] for e in ev.values()],
            "compiles_per_host": compiles,
            "compiles_per_host_mean": sum(compiles) / len(compiles),
            "lanes_per_step_mean": (sum(lanes) / len(lanes)) if lanes
            else 0.0,
            "evps": round(T * MESH_PLACE_FEED / wall) if wall else 0,
            "deploy_s": round(deploy_s, 2),
        }
        fab.close()
        print(f"# mesh placement {policy}: compiles/host="
              f"{placement[policy]['compiles_per_host_mean']:.2f} "
              f"lanes/step={placement[policy]['lanes_per_step_mean']:.1f} "
              f"tenants/host={placement[policy]['tenants_per_host']}",
              file=sys.stderr)
    out["placement"] = {
        "tenants": T, "shapes": MESH_SHAPES, "feed_events": MESH_PLACE_FEED,
        **{f"{k}_{policy}": v
           for policy, p in placement.items() for k, v in p.items()},
        "compile_advantage":
            placement["random"]["compiles_per_host_mean"]
            / max(placement["locality"]["compiles_per_host_mean"], 1e-9),
        "lanes_advantage":
            placement["locality"]["lanes_per_step_mean"]
            / max(placement["random"]["lanes_per_step_mean"], 1e-9),
    }

    # -- 2) scaling: the Kleene anomaly workload over mesh sizes -----------
    sizes = [s for s in (1, 2, 4, 8) if s <= MESH_HOSTS]
    kfeed = gen_events(MESH_FEED)
    krows = [[dev, v] for dev, v, _ in kfeed]
    ktss = [ts for _, _, ts in kfeed]
    scaling = {}
    base_evps = None
    for size in sizes:
        fab = MeshFabric(size, tempfile.mkdtemp(prefix=f"mesh-s{size}-"),
                         MeshConfig(capacity_per_host=MESH_SCALE_TENANTS))
        k = MESH_SCALE_TENANTS * size
        fab.add_tenants([_mesh_kleene_app(i, fleet_ann) for i in range(k)])
        tids = [f"kleene-{i}" for i in range(k)]
        # per-tenant slots: one tenant's callbacks fire on ONE feeder
        # thread, so disjoint slots need no lock (a shared accumulator
        # would lose increments across the per-host threads)
        kmatches = [0] * k
        for j, tid in enumerate(tids):
            fab.add_callback(tid, "Alerts",
                             lambda evs, j=j: kmatches.__setitem__(
                                 j, kmatches[j] + len(evs)))
        # short warm pass (numpy kernels, dictionary encode)
        _mesh_feed_all(fab, tids, krows[:max(MESH_CHUNK, 256)],
                       ktss[:max(MESH_CHUNK, 256)], MESH_CHUNK)
        wall = _mesh_feed_all(fab, tids, krows, ktss, MESH_CHUNK)
        total = k * MESH_FEED
        evps = total / wall if wall else 0.0
        if base_evps is None:
            base_evps = evps
        scaling[str(size)] = {
            "tenants": k, "evps": round(evps),
            "evps_per_chip": round(evps / size),
            "scaling_efficiency": round(evps / (size * base_evps), 3)
            if base_evps else 0.0,
            # REAL Kleene match emissions (counted at the callbacks) —
            # events_in would be ingress, not matches
            "match_total": sum(kmatches),
            "events_in_total": sum(
                e["events_in"] for e in fab.evidence().values()),
        }
        fab.close()
        print(f"# mesh scaling x{size}: {scaling[str(size)]['evps']:,} "
              f"ev/s ({scaling[str(size)]['evps_per_chip']:,}/chip, "
              f"eff={scaling[str(size)]['scaling_efficiency']})",
              file=sys.stderr)
    out["scaling"] = scaling
    out["scaling_efficiency_max_size"] = \
        scaling[str(sizes[-1])]["scaling_efficiency"]
    out["scaling_note"] = (
        "in-process mesh on a shared-GIL container: per-host feeder "
        "threads contend for the same cores, so efficiency here measures "
        "fabric plumbing overhead, not chip scaling — hardware curves "
        "need one OS process per host over the DCN tier")

    # -- 3) live migration under sustained ingest (exactly-once) -----------
    K = 4
    fab = MeshFabric(2, tempfile.mkdtemp(prefix="mesh-mig-"),
                     MeshConfig(capacity_per_host=K))
    fab.add_tenants([_mesh_shape_app(i, 0, fleet_ann) for i in range(K)])
    counts = {i: [] for i in range(K)}
    for i in range(K):
        fab.add_callback(f"mtenant-{i}", "Alerts",
                         lambda evs, i=i: counts[i].extend(
                             tuple(e.data) for e in evs))
    chunks = [(krows[s:s + MESH_CHUNK], ktss[s:s + MESH_CHUNK])
              for s in range(0, MESH_FEED, MESH_CHUNK)]
    half = len(chunks) // 2
    mig_wall = 0.0
    for ci, (c, t) in enumerate(chunks):
        if ci == half:
            src = fab.tenants["mtenant-0"].host
            t0 = time.perf_counter()
            fab.migrate("mtenant-0", 1 - src, reason="bench")
            mig_wall = time.perf_counter() - t0
        for i in range(K):
            fab.send(f"mtenant-{i}", "S", c, t)
    fab.flush()
    mesh_counts = {i: list(counts[i]) for i in range(K)}
    fab.close()
    # solo oracles: each tenant alone on one manager, same feed
    oracle_ok = True
    m = SiddhiManager()
    for i in range(K):
        rt = m.create_siddhi_app_runtime(
            _mesh_shape_app(i, 0, ""), playback=True)
        solo = []
        rt.add_callback("Alerts", StreamCallback(
            lambda evs, solo=solo: solo.extend(tuple(e.data) for e in evs)))
        rt.start()
        ih = rt.input_handler("S")
        for c, t in chunks:
            ih.send_rows([list(r) for r in c], list(t))
        if solo != mesh_counts[i]:
            oracle_ok = False
    m.shutdown()
    out["migration"] = {"tenants": K, "moves": 1,
                        "wall_ms": round(mig_wall * 1e3, 1),
                        "oracle_ok": oracle_ok}
    print(f"# mesh migration: {mig_wall * 1e3:.0f}ms, oracle_ok="
          f"{oracle_ok}", file=sys.stderr)

    # -- 4) elasticity: host leave + rejoin under sustained ingest ---------
    # two FULL hosts (capacity = tenants/2): the join's balanced recompute
    # must shed load onto the newcomer (bulk adoption), the leave must
    # bulk-migrate it back — all exactly-once vs solo oracles
    KE = 6
    fab = MeshFabric(2, tempfile.mkdtemp(prefix="mesh-ela-"),
                     MeshConfig(capacity_per_host=KE // 2))
    fab.add_tenants([_mesh_shape_app(i, i % 2, fleet_ann)
                     for i in range(KE)])
    ecounts = {i: [] for i in range(KE)}
    for i in range(KE):
        fab.add_callback(f"mtenant-{i}", "Alerts",
                         lambda evs, i=i: ecounts[i].extend(
                             tuple(e.data) for e in evs))
    third = len(chunks) // 3
    moves = join_moves = 0
    for ci, (c, t) in enumerate(chunks):
        if ci == third:
            before = fab.migrations
            new_host = fab.add_host(capacity=KE)    # join → bulk adoption
            join_moves = fab.migrations - before
        if ci == 2 * third:
            moves = fab.remove_host(new_host)       # leave → bulk adoption
        for i in range(KE):
            fab.send(f"mtenant-{i}", "S", c, t)
    fab.flush()
    ela_ok = True
    m = SiddhiManager()
    for i in range(KE):
        rt = m.create_siddhi_app_runtime(
            _mesh_shape_app(i, i % 2, ""), playback=True)
        solo = []
        rt.add_callback("Alerts", StreamCallback(
            lambda evs, solo=solo: solo.extend(tuple(e.data) for e in evs)))
        rt.start()
        ih = rt.input_handler("S")
        for c, t in chunks:
            ih.send_rows([list(r) for r in c], list(t))
        if solo != ecounts[i]:
            ela_ok = False
    m.shutdown()
    ela_report = fab.report()
    fab.close()
    out["elasticity"] = {"join_moves": join_moves, "leave_moves": moves,
                         "migrations": ela_report["migrations"],
                         "recoveries": ela_report["recoveries"],
                         "oracle_ok": ela_ok}
    print(f"# mesh elasticity: join moved {join_moves}, leave moved "
          f"{moves}, oracle_ok={ela_ok}", file=sys.stderr)
    print(json.dumps(out))


def child_procmesh() -> None:
    """Process-fabric evidence (ISSUE 16, the MULTICHIP_r07 line): each
    mesh host its OWN OS process with its own JAX runtime, driven over the
    procmesh control socket — per-host-process Kleene scaling curves and a
    real-SIGKILL restart-recovery measurement (supervisor detect → respawn
    → spill replay), exactly-once vs solo oracles."""
    import tempfile

    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.mesh import MeshConfig, MeshFabric

    fleet_ann = f"@app:fleet(batch='{FLEET_BATCH}', lanes='{HOST_LANES}')\n"
    cores = os.cpu_count() or 1
    out = {"hosts": MESH_HOSTS, "mode": "process", "cores": cores}
    # honesty note the guard carries forward: process isolation only buys
    # PARALLEL compute when the container has cores to park workers on —
    # on a 1-core box the curve measures control-socket plumbing, not
    # scaling (the paper's multi-host claim needs >=4 real cores)
    out["core_note"] = (
        f"container has {cores} core(s): with fewer cores than worker "
        f"processes the scaling efficiency is a core-limited plumbing "
        f"number, not a hardware scaling claim")

    # -- 1) per-host-process Kleene scaling --------------------------------
    sizes = [s for s in (1, 2, 4, 8) if s <= MESH_HOSTS]
    kfeed = gen_events(MESH_FEED)
    krows = [[dev, v] for dev, v, _ in kfeed]
    ktss = [ts for _, _, ts in kfeed]
    scaling = {}
    base_evps = None
    for size in sizes:
        t0 = time.perf_counter()
        fab = MeshFabric(size, tempfile.mkdtemp(prefix=f"pmesh-s{size}-"),
                         MeshConfig(capacity_per_host=MESH_SCALE_TENANTS,
                                    mode="process"))
        boot_s = time.perf_counter() - t0
        k = MESH_SCALE_TENANTS * size
        fab.add_tenants([_mesh_kleene_app(i, fleet_ann) for i in range(k)])
        tids = [f"kleene-{i}" for i in range(k)]
        kmatches = [0] * k
        for j, tid in enumerate(tids):
            fab.add_callback(tid, "Alerts",
                             lambda evs, j=j: kmatches.__setitem__(
                                 j, kmatches[j] + len(evs)))
        # short warm pass (child-side numpy kernels, dictionary encode)
        _mesh_feed_all(fab, tids, krows[:max(MESH_CHUNK, 256)],
                       ktss[:max(MESH_CHUNK, 256)], MESH_CHUNK)
        wall = _mesh_feed_all(fab, tids, krows, ktss, MESH_CHUNK)
        fab.flush()
        total = k * MESH_FEED
        evps = total / wall if wall else 0.0
        if base_evps is None:
            base_evps = evps
        scaling[str(size)] = {
            "tenants": k, "evps": round(evps),
            "evps_per_host": round(evps / size),
            "scaling_efficiency": round(evps / (size * base_evps), 3)
            if base_evps else 0.0,
            "match_total": sum(kmatches),
            "worker_boot_s": round(boot_s, 2),
        }
        fab.close()
        print(f"# procmesh scaling x{size}: "
              f"{scaling[str(size)]['evps']:,} ev/s "
              f"({scaling[str(size)]['evps_per_host']:,}/host-process, "
              f"eff={scaling[str(size)]['scaling_efficiency']})",
              file=sys.stderr)
    out["scaling"] = scaling
    out["scaling_efficiency_max_size"] = \
        scaling[str(sizes[-1])]["scaling_efficiency"]

    # -- 2) restart recovery: real SIGKILL mid-ingest ----------------------
    KR = 2
    fab = MeshFabric(2, tempfile.mkdtemp(prefix="pmesh-kill-"),
                     MeshConfig(capacity_per_host=KR, mode="process",
                                snapshot_every_chunks=1,
                                heartbeat_interval_s=0.2))
    fab.add_tenants([_mesh_kleene_app(i, fleet_ann) for i in range(KR)])
    rcounts = {i: [] for i in range(KR)}
    for i in range(KR):
        fab.add_callback(f"kleene-{i}", "Alerts",
                         lambda evs, i=i: rcounts[i].extend(
                             tuple(e.data) for e in evs))
    chunks = [(krows[s:s + MESH_CHUNK], ktss[s:s + MESH_CHUNK])
              for s in range(0, MESH_FEED, MESH_CHUNK)]
    victim = fab.tenants["kleene-0"].host
    t_kill = None
    for ci, (c, t) in enumerate(chunks):
        if ci == len(chunks) // 2:
            t_kill = time.perf_counter()
            fab.kill_host(victim)              # REAL SIGKILL
        for i in range(KR):
            fab.send(f"kleene-{i}", "S", c, t)
    # wait for supervisor respawn + orphan recovery, then drain the spill
    recover_s = None
    deadline = time.time() + 60
    while time.time() < deadline:
        rep = fab.report()
        if all(h["alive"] for h in rep["hosts"].values()) \
                and not rep["spill_backlog"]:
            recover_s = time.perf_counter() - t_kill
            break
        time.sleep(0.1)
    fab.flush()
    rep = fab.report()
    wrk = rep["supervisor"]["workers"][victim]
    proc_counts = {i: list(rcounts[i]) for i in range(KR)}
    fab.close()
    oracle_ok = True
    m = SiddhiManager()
    for i in range(KR):
        rt = m.create_siddhi_app_runtime(
            _mesh_kleene_app(i, ""), playback=True)
        solo = []
        rt.add_callback("Alerts", StreamCallback(
            lambda evs, solo=solo: solo.extend(
                tuple(e.data) for e in evs)))
        rt.start()
        ih = rt.input_handler("S")
        for c, t in chunks:
            ih.send_rows([list(r) for r in c], list(t))
        if solo != proc_counts[i]:
            oracle_ok = False
    m.shutdown()
    out["restart_recovery"] = {
        "tenants": KR, "restarts": wrk["restarts"],
        # kill → fleet healthy again + spill drained (parent clock), plus
        # the PeerHealth-side downtime the supervisor itself observed
        "recover_s": round(recover_s, 2) if recover_s else None,
        "worker_downtime_s": round(wrk.get("last_downtime_s") or 0.0, 2),
        "replayed_chunks": rep["replayed_chunks"],
        "dup_chunks": rep["dup_chunks"],
        "oracle_ok": oracle_ok,
    }
    print(f"# procmesh restart: {wrk['restarts']} restart(s), "
          f"recover={out['restart_recovery']['recover_s']}s, "
          f"replayed={rep['replayed_chunks']}, oracle_ok={oracle_ok}",
          file=sys.stderr)

    # -- 3) federated latency breakdown (ISSUE 18, MULTICHIP_r09 line) -----
    # one parent pull of every worker's phase histograms: per-phase
    # p50/p99 per worker plus the fabric-level merge, with trace
    # stitching sampled 1-in-8 so the parent ring shows journeys that
    # span dispatch -> child transit -> ingress on one trace id.
    FED = min(2, MESH_HOSTS)
    # sample period COPRIME to the tenant round-robin (the tracer's 1-in-N
    # counter is global across sends): an even period with 2 tenants
    # aliases onto tenant 0 forever and worker h1 never sees a trace
    fab = MeshFabric(FED, tempfile.mkdtemp(prefix="pmesh-fed-"),
                     MeshConfig(capacity_per_host=1, mode="process",
                                trace_sample=7))
    fab.add_tenants([_mesh_kleene_app(i, fleet_ann) for i in range(FED)])
    fmatches = [0] * FED
    for i in range(FED):
        fab.add_callback(f"kleene-{i}", "Alerts",
                         lambda evs, i=i: fmatches.__setitem__(
                             i, fmatches[i] + len(evs)))
    for c, t in chunks:
        for i in range(FED):
            fab.send(f"kleene-{i}", "S", c, t)
    fab.flush()
    fed = fab.federation()
    stitched = 0
    if fab.tracer is not None:
        for tr in list(fab.tracer.ring):
            names = {(s.stage, s.name.split(":")[0]) for s in tr.spans}
            if ("procmesh", "dispatch") in names \
                    and ("procmesh", "transit") in names:
                stitched += 1
    fab.close()
    out["latency_breakdown"] = {
        "workers": {w: e["phases"]
                    for w, e in fed["workers"].items() if not e["stale"]},
        "merged": fed["merged"],
        "stale_workers": sorted(w for w, e in fed["workers"].items()
                                if e["stale"]),
        "stitched_journeys": stitched,
        "clock_offsets_ns": fed["clock_offsets_ns"],
    }
    mt = fed["merged"].get("procmesh_transit", {})
    print(f"# procmesh federation: {len(out['latency_breakdown']['workers'])}"
          f" worker(s), transit p50={mt.get('p50_ms')}ms "
          f"p99={mt.get('p99_ms')}ms, stitched={stitched} journey(s)",
          file=sys.stderr)

    # -- 4) parent recovery: real SIGKILL of the PARENT mid-ingest ---------
    # (ISSUE 17, the MULTICHIP_r08 line): the durable fabric runs as its
    # own killable OS process (procmesh.parentmain), is SIGKILLed at a
    # journal/actuate boundary mid-ingest, and a restarted parent against
    # the same root must re-adopt the still-live workers (no restore) and
    # finish the feed byte-identical to solo oracles with zero dup chunks.
    out["parent_recovery"] = _procmesh_parent_recovery()
    print(json.dumps(out))


def _procmesh_parent_recovery() -> dict:
    """One crash/restart cycle of ``siddhi_tpu.procmesh.parentmain``:
    SIGKILL at ``SIDDHI_CRASH_AT=ingest.applied:3`` (mid-feed, after the
    workers are up — the re-adopt path, the one cold-standby HA cannot
    take), then a clean run over the same root. Parent stdio goes to a
    FILE, not a pipe: the orphaned workers inherit the parent's fds, so a
    pipe would never reach EOF after the kill."""
    import signal
    import tempfile

    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.procmesh.parentmain import APP_TMPL, chunk_rows

    P_HOSTS, P_TENANTS, P_CHUNKS, P_WIDTH = 2, 2, 4, 2
    crash_site = os.environ.get("BENCH_PARENT_CRASH_AT", "ingest.applied:3")
    root = tempfile.mkdtemp(prefix="pmesh-parent-")
    logp = os.path.join(root, "parent.log")
    cmd = [sys.executable, "-m", "siddhi_tpu.procmesh.parentmain",
           "--root", root, "--hosts", str(P_HOSTS),
           "--tenants", str(P_TENANTS), "--chunks", str(P_CHUNKS),
           "--width", str(P_WIDTH)]
    env = {k: v for k, v in os.environ.items() if k != "SIDDHI_CRASH_AT"}
    env["JAX_PLATFORMS"] = "cpu"
    res = {"crash_site": crash_site, "hosts": P_HOSTS,
           "tenants": P_TENANTS, "chunks": P_CHUNKS}
    t_kill = None
    with open(logp, "ab") as lf:
        p1 = subprocess.run(cmd, stdout=lf, stderr=lf, cwd=REPO,
                            env={**env, "SIDDHI_CRASH_AT": crash_site},
                            timeout=120)
        t_kill = time.perf_counter()
    res["killed_rc"] = p1.returncode
    if p1.returncode != -signal.SIGKILL:
        res["ok"] = False
        res["error"] = (f"crash run exited {p1.returncode}, expected "
                        f"-SIGKILL at {crash_site}")
        return res
    time.sleep(0.2)
    with open(logp, "ab") as lf:
        p2 = subprocess.run(cmd, stdout=lf, stderr=lf, cwd=REPO, env=env,
                            timeout=120)
    res["restart_wall_s"] = round(time.perf_counter() - t_kill, 2)
    done = None
    if p2.returncode == 0:
        with open(logp, "r", encoding="utf-8", errors="replace") as lf:
            for line in lf:
                if line.startswith("PARENT_DONE "):
                    done = json.loads(line[len("PARENT_DONE "):])
    if done is None:
        res["ok"] = False
        res["error"] = f"restarted parent exited {p2.returncode}"
        return res

    rec = done.get("recovery") or {}
    res.update({
        "recover_s": rec.get("recover_s"),
        "readopted_workers": rec.get("readopted_workers"),
        "restored_workers": rec.get("restored_workers"),
        "readopted_tenants": rec.get("readopted_tenants"),
        "restored_tenants": rec.get("restored_tenants"),
        "journal_records_replayed": rec.get("journal_records_replayed"),
        "journal_lsn": (done.get("journal") or {}).get("lsn"),
        "dup_chunks": done.get("dup_chunks"),
        "applied": done.get("applied"),
    })
    # solo-oracle sink parity: replay the same deterministic chunks
    # through an in-process runtime, dedup the JSONL sink keep-first on
    # the (epoch, idx) identity — byte-exact or the cycle lied
    oracle_ok = all(v == P_CHUNKS for v in (done.get("applied") or {})
                    .values()) and not done.get("dup_chunks")
    m = SiddhiManager()
    for i in range(P_TENANTS):
        rt = m.create_siddhi_app_runtime(APP_TMPL.format(i=i),
                                         playback=True)
        solo = []
        rt.add_callback("Out", StreamCallback(
            lambda evs, solo=solo: solo.extend(list(e.data) for e in evs)))
        rt.start()
        ih = rt.input_handler("S")
        for c in range(P_CHUNKS):
            rows, ts = chunk_rows(c, P_WIDTH)
            ih.send_rows([list(r) for r in rows], list(ts))
        seen, got = set(), []
        try:
            with open(os.path.join(root, f"sink_t{i}.jsonl"),
                      encoding="utf-8") as f:
                for line in f:
                    try:
                        e = json.loads(line)
                    except json.JSONDecodeError:
                        continue          # only a torn final line is legal
                    if (e["e"], e["i"]) not in seen:
                        seen.add((e["e"], e["i"]))
                        got.append(e["d"])
        except OSError:
            pass
        if got != solo:
            oracle_ok = False
    m.shutdown()
    res["oracle_ok"] = oracle_ok
    res["ok"] = bool(oracle_ok
                     and rec.get("readopted_workers", 0)
                     + rec.get("restored_workers", 0) == P_HOSTS)
    print(f"# procmesh parent recovery @{crash_site}: "
          f"recover={res['recover_s']}s readopted_workers="
          f"{res['readopted_workers']} restored_tenants="
          f"{res['restored_tenants']} journal_replayed="
          f"{res['journal_records_replayed']} dup={res['dup_chunks']} "
          f"oracle_ok={oracle_ok}", file=sys.stderr)
    return res


def child_gray() -> None:
    """Gray-failure gauntlet (ISSUE 19, the MULTICHIP_r10 line): a LIVE
    worker that keeps answering heartbeats while every substantive op
    stalls — the failure mode liveness probes cannot see. The latency-
    evidence ladder must classify it *wedged* within a detection budget,
    kill/respawn it, and replay its spill exactly-once, all while the
    innocent tenant on the other host process keeps its throughput.
    Plus a hedge micro-phase: one partitioned reply on a hedge-safe op
    is won by the deadline-budgeted second attempt over a fresh
    connection."""
    import tempfile
    import threading as _th

    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.mesh import MeshConfig, MeshFabric
    from siddhi_tpu.procmesh.protocol import WireChaos, install_wire_chaos

    fleet_ann = f"@app:fleet(batch='{FLEET_BATCH}', lanes='{HOST_LANES}')\n"
    out = {"hosts": 2, "mode": "process", "feed": GRAY_FEED}

    feed = gen_events(GRAY_FEED)
    rows = [[dev, v] for dev, v, _ in feed]
    tss = [ts for _, _, ts in feed]
    chunks = [(rows[s:s + MESH_CHUNK], tss[s:s + MESH_CHUNK])
              for s in range(0, GRAY_FEED, MESH_CHUNK)]
    third = max(1, len(chunks) // 3)

    # -- 1) wedged-worker ladder -------------------------------------------
    # capacity 1 pins the two tenants onto SEPARATE host processes: the
    # innocent tenant's throughput during the wedge window is then a real
    # blast-radius measurement, not a shared-worker artifact
    fab = MeshFabric(2, tempfile.mkdtemp(prefix="pmesh-gray-"),
                     MeshConfig(capacity_per_host=1, mode="process",
                                snapshot_every_chunks=1,
                                heartbeat_interval_s=0.1,
                                io_timeout_s=1.0, wedge_threshold=2,
                                degrade_factor=0.0,  # isolate the wedge rung
                                restart_base_s=0.05))
    fab.add_tenants([_mesh_kleene_app(i, fleet_ann) for i in range(2)])
    gcounts = {i: [] for i in range(2)}
    for i in range(2):
        fab.add_callback(f"kleene-{i}", "Alerts",
                         lambda evs, i=i: gcounts[i].extend(
                             tuple(e.data) for e in evs))
    victim = fab.tenants["kleene-0"].host

    def feed_slice(tid, sl, wall):
        t0 = time.perf_counter()
        for c, t in sl:
            fab.send(tid, "S", c, t)
        wall[tid] = time.perf_counter() - t0

    # calm first third to both tenants
    for c, t in chunks[:third]:
        for i in range(2):
            fab.send(f"kleene-{i}", "S", c, t)
    # wedge the victim's worker: pings keep answering (the stall sits in
    # front of the dispatch lock for substantive ops only), so breaker/
    # heartbeat monitoring alone would call this host healthy forever
    fab.hosts[victim].client.call("wedge", {"stall_s": 60})
    t_wedge = time.time()
    t_wedge_mono = time.perf_counter()
    # middle third from one thread per tenant: the victim's timing-out
    # sends must not serialize in front of the innocent's
    walls = {}
    ths = [_th.Thread(target=feed_slice,
                      args=(f"kleene-{i}", chunks[third:2 * third], walls))
           for i in range(2)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    in_wall = walls["kleene-1"]
    innocent_evps = round(third * MESH_CHUNK / in_wall) if in_wall else 0
    # wait for the FULL ladder: classified -> killed -> respawned
    # (restarts advances) -> tenant recovered onto the fresh child
    h = fab.supervisor.handles[victim]
    heal_s = None
    deadline = time.time() + 60.0
    while time.time() < deadline:
        if h.health.wedge_count >= 1 and h.restarts >= 1 \
                and fab.hosts[victim].alive \
                and "kleene-0" in fab.hosts[victim].runtimes:
            heal_s = time.perf_counter() - t_wedge_mono
            break
        time.sleep(0.05)
    # detection time from the flight ring: injection wall-clock to the
    # decision:worker_wedged stamp (record-before-actuate, so this is the
    # moment the ladder classified, not the kill)
    detection_s = None
    wedge_detail = {}
    for e in fab.supervisor.flight.export(category="procmesh"):
        if e["kind"] == "decision:worker_wedged":
            detection_s = max(0.0, e["t"] - t_wedge)
            wedge_detail = e.get("detail") or {}
            break
    # final third to both, then drain and check exactly-once parity
    for c, t in chunks[2 * third:]:
        for i in range(2):
            fab.send(f"kleene-{i}", "S", c, t)
    fab.flush()
    rep = fab.report()
    wrk = rep["supervisor"]["workers"][victim]
    gray_counts = {i: list(gcounts[i]) for i in range(2)}
    fab.close()
    oracle_ok = True
    m = SiddhiManager()
    for i in range(2):
        rt = m.create_siddhi_app_runtime(
            _mesh_kleene_app(i, ""), playback=True)
        solo = []
        rt.add_callback("Alerts", StreamCallback(
            lambda evs, solo=solo: solo.extend(
                tuple(e.data) for e in evs)))
        rt.start()
        ih = rt.input_handler("S")
        for c, t in chunks:
            ih.send_rows([list(r) for r in c], list(t))
        if solo != gray_counts[i]:
            oracle_ok = False
    m.shutdown()
    out["wedge"] = {
        "tenants": 2,
        "detection_s": round(detection_s, 3)
        if detection_s is not None else None,
        "heal_s": round(heal_s, 2) if heal_s is not None else None,
        "wedge_count": wrk.get("wedge_count"),
        "restarts": wrk["restarts"],
        "op_p99_at_detection_s": wedge_detail.get("op_p99_s"),
        "heartbeat_p99_at_detection_s": wedge_detail.get("heartbeat_p99_s"),
        "replayed_chunks": rep["replayed_chunks"],
        "dup_chunks": rep["dup_chunks"],
        "oracle_ok": oracle_ok,
        "innocent_evps_during_wedge": innocent_evps,
    }
    print(f"# gray wedge: detect={out['wedge']['detection_s']}s "
          f"heal={out['wedge']['heal_s']}s "
          f"restarts={out['wedge']['restarts']} "
          f"dup={rep['dup_chunks']} oracle_ok={oracle_ok} "
          f"innocent={innocent_evps:,} ev/s during wedge",
          file=sys.stderr)

    # -- 2) hedged retry over a partitioned reply --------------------------
    # deterministic wire chaos drops exactly ONE worker->parent reply on a
    # hedge-safe op: the client burns the hedge fraction of the budget,
    # drops the desynced connection, and the fresh-connection second
    # attempt wins — seq-dedup keeps it exactly-once
    fab = MeshFabric(1, tempfile.mkdtemp(prefix="pmesh-hedge-"),
                     MeshConfig(capacity_per_host=4, mode="process",
                                heartbeat_interval_s=0.2,
                                io_timeout_s=4.0))
    chaos = WireChaos(seed=3, drop_recv_p=1.0, ops={"metrics"},
                      fault_budget=1)
    prev = install_wire_chaos(chaos)
    t0 = time.perf_counter()
    try:
        client = fab.hosts[0].client
        rh, _ = client.call("metrics")
        hedge_wall = time.perf_counter() - t0
        out["hedge"] = {
            "op": "metrics",
            "hedge_attempts": client.hedge_attempts,
            "hedge_wins": client.hedge_wins,
            "dropped_recv": chaos.counters["dropped_recv"],
            "hedged_op_wall_s": round(hedge_wall, 3),
            "ok": bool(rh.get("gauges") is not None
                       and client.hedge_wins >= 1),
        }
    finally:
        install_wire_chaos(prev)
        fab.close()
    print(f"# gray hedge: attempts={out['hedge']['hedge_attempts']} "
          f"wins={out['hedge']['hedge_wins']} "
          f"wall={out['hedge']['hedged_op_wall_s']}s",
          file=sys.stderr)
    print(json.dumps(out))


# ---------------------------------------------------------------------------
# parent: orchestration (no jax import — immune to backend-init hangs)
# ---------------------------------------------------------------------------

def _host_baseline() -> dict:
    """The stored host seed numbers (BASELINE.json ``host_baseline``):
    vs_baseline in the host-only fallback branch is computed against the
    recorded seed interpreter rate instead of hardcoding 1.0."""
    try:
        with open(os.path.join(REPO, "BASELINE.json")) as f:
            return json.load(f).get("host_baseline") or {}
    except (OSError, json.JSONDecodeError):
        return {}


def _debug_log(label: str, text: str) -> None:
    """Append a child's full stderr to BENCH_DEBUG.log (round-3 policy: every
    device attempt leaves a diagnosable artifact)."""
    try:
        with open(DEBUG_LOG, "a") as f:
            f.write(f"\n===== {label} @ {time.strftime('%Y-%m-%d %H:%M:%S')} "
                    f"=====\n{text or '(no stderr)'}\n")
    except OSError:
        pass


def _run_child(mode: str, deadline_s: float, env=None, label=None,
               extra=None):
    """Returns (parsed-json | None, error-string | None). A child killed by
    a signal (wedge-kill) reports ``rc=-N`` like any other failure — the
    parent always keeps control of the final JSON line."""
    label = label or mode
    deadline_s = int(deadline_s)
    if deadline_s <= 5:
        return None, f"{label}: skipped (total budget exhausted)"
    cmd = [sys.executable, os.path.abspath(__file__), mode]
    if extra:
        cmd.append(extra)
    try:
        p = subprocess.run(
            cmd, capture_output=True, text=True, timeout=deadline_s,
            env={**os.environ, **(env or {})}, cwd=REPO)
    except subprocess.TimeoutExpired as e:
        err = ""
        if e.stderr:
            err = e.stderr if isinstance(e.stderr, str) else e.stderr.decode(
                errors="replace")
        _debug_log(f"{label} TIMEOUT({deadline_s}s)", err)
        tail = (" | " + " | ".join(err.strip().splitlines()[-4:])) if err else ""
        # the TIMEOUT( prefix is the structured wedge marker the phase
        # sequencer keys on — a fast-failing child whose stderr happens to
        # mention deadlines must not be mistaken for a hang
        return None, (f"TIMEOUT({deadline_s}s) {label}: deadline exceeded "
                      f"(backend hang?){tail}")
    _debug_log(f"{label} rc={p.returncode}", p.stderr)
    sys.stderr.write(p.stderr[-2000:])
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-6:]
        return None, f"{label}: rc={p.returncode}: " + " | ".join(tail)
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            return json.loads(line), None
        except json.JSONDecodeError:
            continue
    return None, f"{label}: no JSON in output"


def run_device_phases(notes: list, smoke_ok: bool,
                      skip_reason_override: str = None) -> tuple:
    """Sequence the device phases, each in its own subprocess under its own
    deadline (clamped to the remaining budget). Returns (merged device dict
    or None, per-phase status dict). Guarantees:

    - later phases gate on the smoke probe (no accelerator costs zero device
      deadline budget);
    - a phase that WEDGES (deadline exceeded) skips the remaining phases —
      the device is presumed gone — but everything already measured stays;
    - a phase that dies fast (rc != 0, including signal kills) costs only
      itself: the next phase still runs;
    - compiled programs persist across phase processes via the JAX
      compilation cache (each child calls ``enable_compile_cache``: the
      directory JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache),
      so each phase pays load-from-cache, not recompile.
    """
    phases: dict = {}
    device: dict = {}
    skip_reason = None if smoke_ok \
        else (skip_reason_override or "smoke failed")
    for ph, deadline in PHASE_DEADLINES:
        if skip_reason is not None:
            phases[ph] = {"status": f"skipped ({skip_reason})"}
            continue
        t0 = time.monotonic()
        res, err = _run_child("--device-child",
                              min(deadline, _remaining() - 10),
                              label=f"device-{ph}", extra=ph)
        entry = {"seconds": round(time.monotonic() - t0, 1)}
        if res is None:
            entry["status"] = "dead"
            entry["error"] = err
            notes.append(f"device {ph} phase failed: {err}")
            if (err or "").startswith("TIMEOUT("):
                # a WEDGE (structured _run_child timeout marker): later
                # phases would hang on the same device — give the budget
                # back instead
                skip_reason = f"{ph} phase wedged"
        else:
            entry["status"] = "ok"
            device.update(res)
        phases[ph] = entry
    return (device if device else None), phases


def main() -> None:
    notes = []
    try:        # fresh debug log per run
        open(DEBUG_LOG, "w").close()
    except OSError:
        pass

    # 1) host baseline FIRST: pinned to the CPU backend (JAX_PLATFORMS=cpu,
    #    so it never claims the chip), it secures the vs_baseline denominator
    #    and the host lines before any device attempt can burn budget
    host, herr = _run_child("--host-child",
                            min(HOST_DEADLINE_S, _remaining() * 0.3),
                            env={"JAX_PLATFORMS": "cpu"})
    if host is None:
        notes.append(f"host baseline failed: {herr}")

    # 1b) multi-tenant fleet scenario: CPU-only like the host child; secures
    #     the shared-compilation / cross-app-lane numbers before any device
    #     attempt can burn budget (BENCH_SKIP_FLEET=1 for device-focused
    #     runs and the bench-robustness tests)
    # 1a) zero-object edge line: bytes-in → rows-out through the columnar
    #     source/sink path + the parallel host tier (CPU-only, like the
    #     host child; BENCH_SKIP_EDGE=1 for device-focused runs)
    edge = None
    if os.environ.get("BENCH_SKIP_EDGE", "") != "1":
        edge, eerr = _run_child("--edge-child",
                                min(EDGE_DEADLINE_S, _remaining() * 0.25),
                                env={"JAX_PLATFORMS": "cpu"})
        if edge is None:
            notes.append(f"edge line failed: {eerr}")
        else:
            if edge.get("objects_per_row", 1) != 0:
                notes.append(
                    f"EDGE OBJECT LEAK: {edge.get('objects_per_row')} "
                    f"Event/StreamEvent constructions per row on the rows "
                    f"path (expected 0)")
            if (edge.get("rows_per_s") or 0) < 1_000_000:
                notes.append(
                    f"edge rows/s {edge.get('rows_per_s'):,} below the "
                    f"1M rows/s target on this container")
            if not edge.get("workers_parity_ok", True):
                notes.append("EDGE WORKERS PARITY MISMATCH: match counts "
                             "diverged across worker counts")
            if (edge.get("workers_speedup_4") or 0) < 2.0:
                notes.append(
                    f"edge workers=4 speedup "
                    f"{edge.get('workers_speedup_4')}x below the 2x target "
                    f"(container numpy 2-thread ceiling "
                    f"{edge.get('thread_ceiling_2')}x on "
                    f"{edge.get('cpus')} cpus)")

    fleet = None
    if os.environ.get("BENCH_SKIP_FLEET", "") != "1":
        fleet, ferr = _run_child("--fleet-child",
                                 min(FLEET_DEADLINE_S, _remaining() * 0.3),
                                 env={"JAX_PLATFORMS": "cpu"})
        if fleet is None:
            notes.append(f"fleet scenario failed: {ferr}")
        else:
            if not fleet.get("oracle_ok"):
                notes.append("FLEET ORACLE MISMATCH: per-tenant match "
                             "counts diverged between fleet/solo/scalar")
            if fleet.get("fleet_vs_solo", 0) < 3.0:
                notes.append(
                    f"fleet_vs_solo {fleet.get('fleet_vs_solo'):.2f}x below "
                    f"the 3x bar at K={fleet.get('tenants')}")

    # 1c) SLO-autopilot storm: CPU-only like the fleet child — premium
    #     p99 vs budget under a 10x noisy neighbour, decisions taken,
    #     sheds landing on best-effort only (BENCH_SKIP_FLEET covers it:
    #     the scenario is a fleet-tier story)
    slo = None
    if os.environ.get("BENCH_SKIP_FLEET", "") != "1":
        slo, slerr = _run_child("--slo-child",
                                min(SLO_DEADLINE_S, _remaining() * 0.25),
                                env={"JAX_PLATFORMS": "cpu"})
        if slo is None:
            notes.append(f"slo storm failed: {slerr}")
        else:
            if not slo.get("in_budget"):
                notes.append(
                    f"SLO BUDGET MISS: premium p99 "
                    f"{slo.get('premium_p99_ms')}ms over the "
                    f"{slo.get('budget_ms')}ms budget after control")
            if slo.get("premium_sheds"):
                notes.append(
                    f"SLO PREMIUM SHEDS: {slo.get('premium_sheds')} "
                    f"premium rows shed (must be 0 — best-effort absorbs)")
            if not slo.get("decisions"):
                notes.append("slo storm took zero decisions (controller "
                             "never engaged?)")

    # 2) smoke: backend init + one tiny op under a short deadline — records
    #    which platform JAX lands on, independent of the full bench
    smoke, serr = _run_child("--smoke-child",
                             min(SMOKE_DEADLINE_S, _remaining() * 0.1))
    if smoke is None:
        notes.append(f"smoke failed: {serr}")

    # 3) device phases: smoke gates them (no accelerator costs zero device
    #    budget), then compile → throughput → latency → oracle each run in
    #    their own subprocess under their own deadline. A wedge costs one
    #    phase (plus skipping the rest), never the parent's JSON line.
    #    A smoke that lands on the CPU backend means no accelerator exists
    #    in this container: running the device phases there would burn the
    #    budget producing platform=cpu numbers that read as device
    #    evidence (and would feed the latency guard garbage) — skip, and
    #    say so (BENCH_FORCE_DEVICE=1 overrides for debugging).
    smoke_ok = smoke is not None
    skip_reason = None
    force = os.environ.get("BENCH_FORCE_DEVICE", "") == "1" \
        or os.environ.get("BENCH_PHASE_KILL") \
        or os.environ.get("BENCH_PHASE_WEDGE")   # phase-machinery test
    # hooks exercise the sequencer itself — they must run on any backend
    if smoke_ok and smoke.get("platform") == "cpu" and not force:
        smoke_ok = False
        skip_reason = "no accelerator (smoke platform=cpu)"
        notes.append("device phases skipped: smoke landed on the CPU "
                     "backend (no accelerator in this container)")
    device, device_phases = run_device_phases(notes, smoke_ok, skip_reason)

    metric = f"{N_STATES}-state partitioned pattern throughput"
    smoke_field = smoke if smoke else {"ok": False, "error": serr}

    def host_fields(out: dict) -> None:
        """Host execution-tier lines shared by both result branches."""
        if not host:
            return
        out["host_scalar_rate"] = round(host["rate"])
        if host.get("host_batch_rate"):
            out["host_batch_rate"] = round(host["host_batch_rate"])
            out["host_engine"] = host.get("host_engine")
            parity_ok = host.get("host_batch_oracle_matches") == \
                host.get("oracle_matches")
            out["host_parity"] = {
                "scalar": host.get("oracle_matches"),
                "columnar": host.get("host_batch_oracle_matches"),
                "events": ORACLE_EVENTS,
                "ok": parity_ok,
            }
            if not parity_ok:
                notes.append(
                    f"HOST ORACLE MISMATCH: columnar="
                    f"{host.get('host_batch_oracle_matches')} scalar="
                    f"{host.get('oracle_matches')} over {ORACLE_EVENTS}")
        elif host.get("host_batch_error"):
            out["host_engine"] = "scalar"
            notes.append(f"host_batch failed: {host['host_batch_error']}")
    if device and host and device.get("rate"):
        # oracle parity is judged only when the oracle phase produced a
        # count — a dead oracle phase reports as such, not as a mismatch
        oracle_ok = (device.get("oracle_matches") is not None
                     and device.get("oracle_matches")
                     == host.get("oracle_matches"))
        out = {
            "metric": metric,
            "value": round(device["rate"]),
            "unit": "events/sec",
            "vs_baseline": round(device["rate"] / host["rate"], 2),
            "p99_detection_latency_ms": device.get("p99_ms"),
            "p50_detection_latency_ms": device.get("p50_ms"),
            "offered_evps": device.get("offered_evps"),
            "latency_budget_ms": device.get("latency_budget_ms"),
            "latency_curve": device.get("latency_curve"),
            "latency_mode_capacity_evps":
                device.get("latency_mode_capacity_evps"),
            "oracle_matches_checked": oracle_ok,
            "oracle_matches": {"device": device.get("oracle_matches"),
                               "host": host.get("oracle_matches"),
                               "events": ORACLE_EVENTS},
            "device_step_ms": device.get("step_ms"),
            "d2h_roundtrip_ms": device.get("roundtrip_ms"),
            "pack_rate_evps": (round(DEVICE_EVENTS / device["pack_s"])
                               if device.get("pack_s") else None),
            "end_to_end_rate": device.get("overlapped_rate"),
            "ingest_overlap_efficiency": device.get("overlap_efficiency"),
            "pack_hidden_frac": device.get("pack_hidden_frac"),
            "device_idle_frac": device.get("device_idle_frac"),
            "ingress": device.get("ingress"),
            "drops": device.get("drops"),
            "timing_fence": device.get("fence"),
            "platform": device.get("platform"),
            "device_ok": True,
            "baseline": "repo host interpreter (single-threaded Python; "
                        "no JVM in image — flatters vs_baseline vs real "
                        "siddhi-core)",
            "baseline_derating": {
                "note": "no JVM in this image; reference perf harnesses "
                        "(SimpleFilterSingleQueryPerformance) report ~1-10M "
                        "ev/s for SIMPLE filters on laptop JVMs, and "
                        "multi-state partitioned patterns run far slower; "
                        "a 10-20x JVM-over-CPython multiplier on this "
                        "workload is the defensible band",
                "assumed_jvm_multiplier": 15,
                "vs_jvm_estimate": round(
                    device["rate"] / (host["rate"] * 15), 2),
            },
        }
        host_fields(out)
        if device.get("adaptive"):
            out["adaptive_batch_size"] = device["adaptive"]["batch_size"]
            out["adaptive"] = device["adaptive"]
        if device.get("latency_mode"):
            # the latency-mode line: offered rate, p50/p99, chosen window
            out["latency_mode"] = device["latency_mode"]
        if device.get("latency_breakdown"):
            # the X-Ray attribution line: per-phase p99s reconciled
            # against the end-to-end mean + deadline-queueing share
            out["latency_breakdown"] = device["latency_breakdown"]
        if device.get("oracle_matches") is not None and not oracle_ok:
            notes.append(
                f"ORACLE MISMATCH: device={device.get('oracle_matches')} "
                f"host={host.get('oracle_matches')} over {ORACLE_EVENTS}")
    elif host:
        # host-only fallback: the headline number is the best host tier
        # (columnar when it engaged), and vs_baseline compares against the
        # RECORDED seed interpreter rate (BASELINE.json host_baseline)
        # instead of the old hardcoded 1.0
        best = max(host["rate"], host.get("host_batch_rate") or 0.0)
        seed = _host_baseline()
        seed_evps = seed.get("scalar_evps")
        out = {
            "metric": metric + " (HOST-ONLY FALLBACK: device unavailable)",
            "value": round(best),
            "unit": "events/sec",
            "vs_baseline": round(best / seed_evps, 2) if seed_evps else 1.0,
            "baseline": f"BASELINE.json host_baseline.scalar_evps="
                        f"{seed_evps} (seed scalar interpreter)"
                        if seed_evps else "same-run scalar interpreter",
            "device_ok": False,
        }
        host_fields(out)
        if device:
            # phases that DID complete before the round died still count
            # as evidence (compile/step times, partial latency numbers)
            out["device_partial"] = device
    else:
        out = {"metric": metric, "value": 0, "unit": "events/sec",
               "vs_baseline": 0.0, "device_ok": False}
        if device:
            out["device_partial"] = device
    if fleet:
        out["fleet"] = fleet
    if slo:
        out["slo"] = slo
    if edge:
        out["edge"] = edge
    out["device_phases"] = device_phases
    out["smoke"] = smoke_field
    if BENCH_METRICS and host and host.get("metrics"):
        out["metrics_snapshot"] = host["metrics"]
    if notes:
        out["notes"] = notes
    print(json.dumps(out))
    # the JSON line above is always printed; the status says whether the
    # chip was measured (a host-only line is not a device benchmark)
    on_chip = smoke is not None and smoke.get("platform") != "cpu"
    if not on_chip or any(ph.get("status") != "ok"
                          for ph in device_phases.values()):
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--smoke-child":
        child_smoke()
    elif len(sys.argv) > 1 and sys.argv[1] == "--device-child":
        child_device(sys.argv[2] if len(sys.argv) > 2 else "all")
    elif len(sys.argv) > 1 and sys.argv[1] == "--host-child":
        child_host()
    elif len(sys.argv) > 1 and sys.argv[1] == "--fleet-child":
        child_fleet()
    elif len(sys.argv) > 1 and sys.argv[1] == "--slo-child":
        child_slo()
    elif len(sys.argv) > 1 and sys.argv[1] == "--edge-child":
        child_edge()
    elif len(sys.argv) > 1 and sys.argv[1] == "--mesh-child":
        child_mesh()
    elif len(sys.argv) > 1 and sys.argv[1] == "--procmesh-child":
        child_procmesh()
    elif len(sys.argv) > 1 and sys.argv[1] == "--gray-child":
        child_gray()
    else:
        main()
