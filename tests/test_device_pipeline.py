"""Double-buffered async device pipeline (PR 7 tentpole).

Pins, on the CPU backend (always runnable in CI):

- ordering/parity: the pipelined driver (dispatch fire-and-forget, fence at
  the egress edge only) emits byte-identical matches, in order, vs the
  synchronous device path — over a 200k-event filter corpus and a stateful
  pattern corpus;
- snapshot/restore with a NON-EMPTY ring (staged batches checkpoint and
  replay exactly once);
- flush-cause accounting incl. the latency-mode "deadline" flush;
- AIMD latency mode: the window shrinks under an injected slow step and the
  flush deadline tracks the remaining budget;
- DeviceGuard mid-pipeline faults: a chaos-injected device failure replays
  at its own FIFO egress slot — no reorder, no double emit (satellite fix:
  the guard used to assume synchronous ``rt.process``).
"""

import random
import time

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback


def _gen_rows(n, seed=42):
    rng = random.Random(seed)
    return [[f"dev{rng.randrange(16)}", round(rng.uniform(0.0, 100.0), 3)]
            for _ in range(n)]


def _run_app(app, rows, base_ts=1_000_000, flush=True):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: got.extend(tuple(e.data) for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    for i, r in enumerate(rows):
        ih.send(list(r), timestamp=base_ts + i)
    if flush:
        rt.flush_device()
    m.shutdown()
    return got


# --------------------------------------------------------------- parity

FILTER_ASYNC = """
define stream S (dev string, v double);
@device(batch='4096', async='true')
from S[v > 90.0] select dev, v insert into Alerts;
"""
FILTER_SYNC = FILTER_ASYNC.replace(", async='true'", "")


def test_pipelined_filter_parity_200k():
    """Double-buffered vs synchronous stepping over the 200k corpus:
    byte-identical rows, in emission order (the egress edge is FIFO)."""
    rows = _gen_rows(200_000)
    got_async = _run_app(FILTER_ASYNC, rows)
    got_sync = _run_app(FILTER_SYNC, rows)
    assert got_async == got_sync
    assert len(got_sync) == sum(1 for r in rows if r[1] > 90.0)


PATTERN_ASYNC = """
define stream S (dev string, v double);
@device(batch='1024', slots='64', async='true')
from every e1=S[v > 90.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v] within 4000
select e1.v as v1, e2.v as v2, e3.v as v3 insert into Alerts;
"""
PATTERN_SYNC = PATTERN_ASYNC.replace(", async='true'", "")


def test_pipelined_pattern_parity():
    """Stateful NFA under the pipeline: donated state round-trips through
    overlapped steps without corrupting match semantics."""
    rows = _gen_rows(20_000, seed=7)
    got_async = _run_app(PATTERN_ASYNC, rows)
    got_sync = _run_app(PATTERN_SYNC, rows)
    assert got_async == got_sync
    assert got_sync          # the corpus produces matches


def test_pipeline_window_one_matches_window_two():
    """@device(pipeline='1') serializes dispatch/egress — same output."""
    rows = _gen_rows(8_000, seed=11)
    app_w1 = PATTERN_ASYNC.replace("async='true'",
                                   "async='true', pipeline='1'")
    assert _run_app(app_w1, rows) == _run_app(PATTERN_SYNC, rows)


# ------------------------------------------------------- driver mechanics

def test_driver_overlap_counters_and_gauges():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(FILTER_ASYNC, playback=True)
    rt.start()
    bridge = rt.device_bridges[0]
    drv = bridge.driver
    assert drv is not None and drv.window == 2
    ih = rt.input_handler("S")
    for i, r in enumerate(_gen_rows(20_000, seed=3)):
        ih.send(r, timestamp=1_000_000 + i)
    rt.flush_device()
    assert drv.batches_stepped >= 4
    assert drv.step_seconds > 0.0
    assert drv.pipeline_depth == 0          # drained
    sm = rt.ctx.statistics_manager
    q = bridge.query_name
    assert sm.gauges[f"device.{q}.pipeline_depth"].value == 0
    # every stepped batch left its serial segments in the phase trackers:
    # builders stamped the pack, the driver the queue and the dispatch, the
    # runtime's collect its fence and its decode (event-weighted counts)
    trackers = bridge.probe.phases.trackers
    for phase in ("pack", "ingress_queue", "device_step", "egress_fence",
                  "egress_decode"):
        assert trackers[phase].count == 20_000, phase
    # rows went out under the engine lock: asked for, then held
    assert trackers["lock_wait"].count == trackers["sink_publish"].count > 0
    assert trackers["ring_wait"].count == 0     # the ring never filled
    m.shutdown()


def test_snapshot_restore_with_nonempty_ring():
    """Batches staged in the driver ring at snapshot time checkpoint as
    'staged' and replay exactly once on restore — the cut is consistent
    (the exact walk `_pre_snapshot` performs after pausing the driver)."""
    app = """
    define stream S (v long);
    @device(batch='4', async='true')
    from S#window.length(8) select sum(v) as t insert into Alerts;
    """
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: got.extend(e.data[0] for e in evs)))
    rt.start()
    bridge = rt.device_bridges[0]
    ih = rt.input_handler("S")
    for i in range(8):                  # two full batches, delivered
        ih.send([i], timestamp=1000 + i)
    rt.flush_device()
    delivered = list(got)
    bridge.driver.pause()               # freeze the worker
    for i in range(8, 18):              # 2 full batches into the ring +
        ih.send([i], timestamp=1000 + i)    # 2 rows left in the builder
    assert bridge.driver.pipeline_depth >= 2        # ring is NON-empty
    holder = rt.ctx.state_registry[f"device-{bridge.query_name}"]
    snap = holder.snapshot_state()
    assert len(snap["staged"]) >= 2
    assert snap["builder"]["n"] == 2
    bridge.driver.resume()      # let shutdown drain instead of timing out
    m.shutdown()

    # restore into a fresh runtime: staged + builder rows replay once
    m2 = SiddhiManager()
    rt2 = m2.create_siddhi_app_runtime(app, playback=True)
    got2 = []
    rt2.add_callback("Alerts", StreamCallback(
        lambda evs: got2.extend(e.data[0] for e in evs)))
    rt2.start()
    b2 = rt2.device_bridges[0]
    rt2.ctx.state_registry[f"device-{b2.query_name}"].restore_state(snap)
    b2.driver.resume()
    rt2.flush_device()
    m2.shutdown()

    # uninterrupted oracle
    m3 = SiddhiManager()
    rt3 = m3.create_siddhi_app_runtime(app, playback=True)
    got3 = []
    rt3.add_callback("Alerts", StreamCallback(
        lambda evs: got3.extend(e.data[0] for e in evs)))
    rt3.start()
    ih3 = rt3.input_handler("S")
    for i in range(18):
        ih3.send([i], timestamp=1000 + i)
    rt3.flush_device()
    m3.shutdown()
    assert delivered + got2 == got3


# --------------------------------------------------- latency mode / AIMD

def test_latency_mode_window_shrinks_under_slow_step():
    """An injected slow step pushes predicted p99 over the budget — the
    controller halves the window toward min_batch."""
    from siddhi_tpu.flow.adaptive_batch import AdaptiveBatchController
    ctrl = AdaptiveBatchController(min_batch=64, max_batch=4096,
                                   initial=4096, cooldown=1,
                                   latency_target_ms=50.0)
    assert ctrl.mode == "latency"
    for _ in range(12):
        ctrl.observe(ctrl.current, 0.2)     # 200ms steps: way over budget
    assert ctrl.current == 64
    # budget is consumed by the slow step: deadline floors at 1ms
    assert ctrl.flush_deadline_ms == 1.0


def test_latency_mode_window_grows_when_under_budget():
    from siddhi_tpu.flow.adaptive_batch import AdaptiveBatchController
    ctrl = AdaptiveBatchController(min_batch=64, max_batch=4096,
                                   initial=64, cooldown=1,
                                   latency_target_ms=100.0)
    for _ in range(12):
        ctrl.observe(ctrl.current, 0.0005)  # fast steps, full batches
    assert ctrl.current > 64
    assert ctrl.predicted_p99_ms < 100.0
    rep = ctrl.report()
    assert rep["mode"] == "latency"
    assert rep["latency_target_ms"] == 100.0


def test_deadline_flush_bounds_partial_batch_wait():
    """Latency mode + async pipeline: a partial batch flushes on the
    wall-clock deadline — no capacity flush, no explicit flush_device —
    and the probe accounts it under the 'deadline' cause."""
    app = """
    @app:adaptive(latency.target.ms='40')
    define stream S (v double);
    @device(batch='4096', async='true')
    from S[v > 0.0] select v insert into Alerts;
    """
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: got.extend(e.data[0] for e in evs)))
    rt.start()
    bridge = rt.device_bridges[0]
    assert bridge.runtime.batch_controller.mode == "latency"
    ih = rt.input_handler("S")
    for i in range(3):
        ih.send([float(i + 1)], timestamp=1000 + i)
    deadline = time.time() + 10.0
    while len(got) < 3 and time.time() < deadline:
        time.sleep(0.02)
    assert got == [1.0, 2.0, 3.0]
    assert bridge.probe.flush_causes.get("deadline", 0) >= 1
    m.shutdown()


def test_flush_cause_accounting_capacity_and_drain():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
    define stream S (v double);
    @device(batch='8', async='true')
    from S[v > 0.0] select v insert into Alerts;
    """, playback=True)
    rt.start()
    bridge = rt.device_bridges[0]
    ih = rt.input_handler("S")
    for i in range(20):                 # 2 capacity flushes + 4 staged
        ih.send([float(i + 1)], timestamp=1000 + i)
    rt.flush_device()                   # drain flush for the partial
    causes = bridge.probe.flush_causes
    assert causes.get("capacity", 0) >= 2
    assert causes.get("drain", 0) >= 1
    m.shutdown()


# ----------------------------------------------------- guard / chaos

@pytest.mark.chaos
def test_chaos_mid_pipeline_fault_exactly_once_in_order():
    """A device fault mid-pipeline replays the failed batch's shadow at its
    own FIFO egress slot: output equals the fault-free run exactly — same
    rows, same order, no loss, no double emit."""
    chaos_app = """
    @app:chaos(seed='5', device.fail.p='0.25')
    @app:resilience(device.circuit.threshold='3',
                    device.circuit.cooldown.ms='30')
    define stream S (dev string, v double);
    @device(batch='16', async='true', strict='true')
    from S[v > 50.0] select dev, v insert into Alerts;
    """
    clean_app = """
    define stream S (dev string, v double);
    @device(batch='16', async='true', strict='true')
    from S[v > 50.0] select dev, v insert into Alerts;
    """
    rows = _gen_rows(600, seed=13)
    got_chaos = _run_app(chaos_app, rows)
    got_clean = _run_app(clean_app, rows)
    # normalize float width: the device path carries v as f32, the host
    # replay emits the raw python float — same value, different repr
    norm = lambda out: [(d, round(v, 3)) for d, v in out]   # noqa: E731
    assert norm(got_chaos) == norm(got_clean)


def test_guard_counts_pipeline_fallbacks():
    app = """
    @app:chaos(seed='9', device.fail.p='0.5')
    @app:resilience(device.circuit.threshold='100')
    define stream S (v double);
    @device(batch='8', async='true', strict='true')
    from S[v > 0.0] select v insert into Alerts;
    """
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: got.extend(e.data[0] for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    for i in range(160):
        ih.send([float(i + 1)], timestamp=1000 + i)
    rt.flush_device()
    guard = rt.device_bridges[0].guard
    assert guard.failures > 0
    assert guard.fallback_events > 0
    assert guard.lost_events == 0
    assert sorted(got) == [float(i + 1) for i in range(160)]
    m.shutdown()
