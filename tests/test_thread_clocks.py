"""A CPU clock beside the wall clock (ISSUE 37): every segment a host thread
works in reads ``time.thread_time`` where it reads ``time.perf_counter``, so
a tracker says how much of its time the thread ran and how much it waited;
the client's thread is read from seal to seal, the driver thread whole, and
``publish`` is split into what the engine builds and the rest.

CPU only; no time is held against a fixed number, only against another
clock read in the same test.
"""

import inspect
import threading
import time

import pytest

from siddhi_tpu import QueryCallback, SiddhiManager, StreamCallback
from siddhi_tpu.observability.phases import (
    CPU_OF,
    NESTED,
    PHASES,
    THREAD_CLOCKS,
    PhaseBreakdown,
)
from test_step_runtime import KINDS, _deploy, _feed

APP = """
@app(name='Clocks')
define stream S (v double);
define stream O (v double, t double);
@info(name='agg')
@device(batch='64'{extra}) from S[v >= 0.0]#window.length(16)
select v, sum(v) as t insert into O;
"""
BATCH = 64
COMPANIONS = {"device_step_cpu": "device_step", "route_cpu": "route",
              "key_lookup_cpu": "key_lookup",
              "egress_fence_cpu": "egress_fence",
              "egress_decode_cpu": "egress_decode",
              "sink_publish_cpu": "sink_publish"}


@pytest.fixture()
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def _runtime(manager, async_mode, subscribe=None):
    rt = manager.create_siddhi_app_runtime(
        APP.format(extra=", async='true'" if async_mode else ""),
        playback=True)
    (subscribe or (lambda r: r.add_callback(
        "O", StreamCallback(lambda evs: None))))(rt)
    rt.start()
    return rt, rt.device_bridges[0]


def _send(rt, n, start=0):
    ih = rt.input_handler("S")
    for i in range(start, start + n):
        ih.send([float(i)], timestamp=1000 + i)


def _sum_count(bridge, name):
    h = bridge.probe.phases.trackers[name].hist
    return h.sum, h.count


def _mean_since(bridge, name, before):
    s, c = _sum_count(bridge, name)
    assert c > before[1], f"nothing recorded in '{name}'"
    return (s - before[0]) / (c - before[1])


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------

def test_the_table_names_a_companion_for_each_segment_a_thread_works_in():
    assert CPU_OF == {wall: cpu for cpu, wall in COMPANIONS.items()}
    assert len(set(THREAD_CLOCKS)) == len(THREAD_CLOCKS)
    assert set(THREAD_CLOCKS) - set(COMPANIONS) \
        == {"client_cycle", "client_cpu", "driver_cpu"}
    # waits by definition have none, pack lies inside the client's cycle
    assert not {"lock_wait", "ring_wait", "pack"} & set(CPU_OF)
    # one vocabulary, no name twice
    assert not set(THREAD_CLOCKS) & (set(PHASES) | set(NESTED))
    assert NESTED["publish_build"] == "sink_publish"


class _Tracker:
    def __init__(self):
        self.samples = []

    def record_seconds(self, seconds, n=1, exemplar=None):
        self.samples.append((seconds, n))


def test_record_batch_records_a_companion_wherever_its_wall_tracker_is():
    made = {}
    pb = PhaseBreakdown(lambda name: made.setdefault(name, _Tracker()))
    # a zero of CPU is a reading; a zero of wall is a segment that was not
    pb.record_batch(8, step_s=0.002, step_cpu_s=0.0, fence_s=0.001,
                    fence_cpu_s=0.0004, decode_s=0.0, decode_cpu_s=0.0,
                    publish_s=0.003, publish_cpu_s=0.003,
                    parts={"publish_build": 0.0},
                    client_cycle_s=0.01, client_cpu_s=0.004,
                    driver_cpu_s=0.005)
    # nobody read the CPU clock (a host tier): the wall alone
    pb.record_batch(4, step_s=0.002)
    # a batch another thread sealed carries no cycle of the client's
    pb.record_batch(2, step_s=0.002, step_cpu_s=0.001, driver_cpu_s=0.0)
    assert made["device_step"].samples == [(0.002, 8), (0.002, 4), (0.002, 2)]
    assert made["device_step_cpu"].samples == [(0.0, 8), (0.001, 2)]
    assert made["egress_fence_cpu"].samples == [(0.0004, 8)]
    assert made["egress_decode"].samples == [] \
        and made["egress_decode_cpu"].samples == []
    assert made["route_cpu"].samples == []
    assert made["sink_publish_cpu"].samples == [(0.003, 8)]
    assert made["publish_build"].samples == [(0.0, 8)]
    assert made["client_cycle"].samples == [(0.01, 8)]
    assert made["client_cpu"].samples == [(0.004, 8)]
    assert made["driver_cpu"].samples == [(0.005, 8), (0.0, 2)]
    # the serial sum holds the wall segments alone
    assert made["end_to_end"].samples[0] == (pytest.approx(0.006), 8)


# ---------------------------------------------------------------------------
# a thread that sleeps, a thread that spins
# ---------------------------------------------------------------------------

def _spin(cpu_s):
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("async_mode", [True, False], ids=["async", "sync"])
def test_a_sleep_reads_as_wait_and_a_spin_reads_as_cpu(manager, async_mode):
    """``dispatch`` sleeps 50 ms: ``device_step`` holds them on the wall and
    under half of them as CPU. The decode spins until its thread's CPU clock
    has advanced 30 ms: ``egress_decode`` holds at least 30 on both."""
    rt, bridge = _runtime(manager, async_mode)
    r = bridge.runtime
    _send(rt, BATCH)            # the batch that compiles the step
    rt.flush_device()
    names = ("device_step", "device_step_cpu", "egress_decode",
             "egress_decode_cpu")
    before = {n: _sum_count(bridge, n) for n in names}
    dispatch, decode = r.dispatch, r._decode

    def slept(batch):
        time.sleep(0.05)
        return dispatch(batch)

    def spun(out):
        _spin(0.03)
        return decode(out)

    r.dispatch, r._decode = slept, spun
    _send(rt, BATCH, start=BATCH)
    rt.flush_device()
    mean = {n: _mean_since(bridge, n, before[n]) for n in names}
    assert mean["device_step"] >= 0.05
    assert mean["device_step_cpu"] < mean["device_step"] / 2
    assert mean["egress_decode"] >= 0.03
    assert mean["egress_decode_cpu"] >= 0.03
    assert mean["egress_decode_cpu"] <= mean["egress_decode"] + 0.005
    rep = rt.observability.latency_report()["queries"]["agg"]
    assert rep["phases"]["device_step"]["off_cpu_share"] > 0.0


# ---------------------------------------------------------------------------
# one companion a batch, whatever the plan and the path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sync", "async"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_each_companion_counts_what_its_wall_tracker_counts(manager, kind,
                                                            mode):
    app, events, batch, options, _ordered = KINDS[kind]
    a = ", async='true'" if mode == "async" else ""
    rt, _got = _deploy(
        manager, app, f"@device(strict='true', batch='{batch}'{options}{a})")
    bridge, = rt.device_bridges
    rt.start()
    _feed(rt, events)
    rt.flush_device()
    trackers = bridge.probe.phases.trackers
    for cpu, wall in COMPANIONS.items():
        assert trackers[cpu].count == trackers[wall].count, (cpu, wall)
        # what a thread ran is no longer than the stretch it ran in, bar
        # the clocks' own resolution a batch
        batches = bridge.probe.steps
        assert trackers[cpu].hist.sum <= trackers[wall].hist.sum \
            + 1e-4 * batches * batch, cpu
    assert trackers["device_step_cpu"].count == len(events)
    assert (trackers["route_cpu"].count > 0) == (kind == "partition")
    assert (trackers["key_lookup_cpu"].count > 0) == (kind == "keyed")
    # the publishing is timed where the driver does it; the sync path
    # delivers behind its phases record
    assert (trackers["sink_publish_cpu"].count > 0) == (mode == "async")
    assert trackers["publish_build"].count == trackers["sink_publish"].count
    assert (trackers["driver_cpu"].count > 0) == (mode == "async")
    # one thread sealed every batch: all but the first carry its cycle
    sealed = trackers["device_step"].count
    first = min(batch, len(events))
    assert trackers["client_cpu"].count == trackers["client_cycle"].count \
        <= sealed - first
    rep = rt.observability.latency_report()["queries"][bridge.query_name]
    assert rep["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-5)
    assert rep["end_to_end"]["count"] == len(events)


@pytest.mark.parametrize("kind, other", [("partition", "key_lookup"),
                                         ("keyed", "route")])
def test_the_latency_report_says_ran_and_waited_for_the_tables_phases(
        manager, kind, other):
    """A served partition runs every segment the table names but the
    other runtime's way to its keys: a pattern's lane layout (``route``)
    or a keyed window's directory (``key_lookup``)."""
    app, events, batch, options, _ordered = KINDS[kind]
    rt, _got = _deploy(
        manager, app,
        f"@device(strict='true', batch='{batch}'{options}, async='true')")
    bridge, = rt.device_bridges
    rt.start()
    _feed(rt, events)
    rt.flush_device()
    rep = rt.observability.latency_report()["queries"][bridge.query_name]
    with_cpu = {p for p, v in rep["phases"].items() if "cpu_ms" in v}
    assert with_cpu == set(CPU_OF) - {other}
    assert {p for p, v in rep["phases"].items() if "off_cpu_share" in v} \
        == set(CPU_OF) - {other}
    for p in with_cpu:
        entry = rep["phases"][p]
        assert entry["cpu_ms"] >= 0.0
        assert 0.0 <= entry["off_cpu_share"] <= 1.0
    # the threads' trackers are read at the top of the entry, not as phases
    assert not set(THREAD_CLOCKS) & set(rep["phases"])
    assert rep["client_cpu_us_per_event"] > 0.0
    assert 0.0 <= rep["client_off_cpu_share"] <= 1.0
    assert rep["driver_cpu_ms_per_batch"] > 0.0
    assert rep["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-5)
    # a synchronous query has no driver thread to read
    rt2, bridge2 = _runtime(manager, False)
    _send(rt2, BATCH * 3)
    rt2.flush_device()
    rep2 = rt2.observability.latency_report()["queries"]["agg"]
    assert "driver_cpu_ms_per_batch" not in rep2
    assert "sink_publish" not in rep2["phases"]
    assert {"device_step", "egress_fence", "egress_decode"} \
        == {p for p, v in rep2["phases"].items() if "cpu_ms" in v}


# ---------------------------------------------------------------------------
# the client's thread, seal to seal
# ---------------------------------------------------------------------------

def test_a_client_cycle_is_read_only_between_two_seals_of_one_thread(manager):
    rt, bridge = _runtime(manager, True)
    trackers = bridge.probe.phases.trackers

    def cycles():
        rt.flush_device()
        assert trackers["client_cpu"].count == trackers["client_cycle"].count
        return trackers["client_cycle"].count

    _send(rt, BATCH)                    # the first seal: nothing before it
    assert cycles() == 0
    _send(rt, BATCH, start=BATCH)       # sealed by the thread that sealed
    assert cycles() == BATCH            # the first
    _send(rt, 10, start=2 * BATCH)      # staged by this thread ...
    other = threading.Thread(target=rt.flush_device)
    other.start()                       # ... and sealed by another
    other.join(timeout=60.0)
    assert not other.is_alive()
    assert trackers["device_step"].count == 2 * BATCH + 10
    assert cycles() == BATCH
    _send(rt, BATCH, start=3 * BATCH)   # this thread again, but the seal
    assert cycles() == BATCH            # before it was not its own
    _send(rt, BATCH, start=4 * BATCH)
    assert cycles() == 2 * BATCH
    # the stretch holds the whole of what the thread did for the batch:
    # at least the CPU it used, and the CPU is above nought for 64 sends
    assert trackers["client_cpu"].hist.sum > 0.0
    assert trackers["client_cpu"].hist.sum \
        <= trackers["client_cycle"].hist.sum + 1e-3 * 2 * BATCH


def test_a_client_that_waits_between_sends_reads_the_wait_off_its_cpu(manager):
    rt, bridge = _runtime(manager, True)
    _send(rt, BATCH)
    _send(rt, BATCH - 1, start=BATCH)
    time.sleep(0.05)                    # a paced producer
    _send(rt, 1, start=2 * BATCH - 1)
    rt.flush_device()
    trackers = bridge.probe.phases.trackers
    assert trackers["client_cycle"].count == BATCH
    cycle = trackers["client_cycle"].hist.sum / BATCH
    cpu = trackers["client_cpu"].hist.sum / BATCH
    assert cycle >= 0.05 and cpu < cycle / 2
    rep = rt.observability.latency_report()["queries"]["agg"]
    assert rep["client_off_cpu_share"] > 0.5
    # seconds a batch over events a batch
    assert rep["client_cpu_us_per_event"] == pytest.approx(
        cpu / BATCH * 1e6, rel=1e-3)


# ---------------------------------------------------------------------------
# a guard replay
# ---------------------------------------------------------------------------

def test_a_guard_replay_records_no_companion(manager):
    rt, bridge = _runtime(manager, True)
    compiled = bridge.runtime.compiled
    inner, left = compiled.decode_outputs, [1]

    def decode_once_broken(out):
        if left[0]:
            left[0] -= 1
            raise RuntimeError("sabotaged decode")
        return inner(out)

    compiled.decode_outputs = decode_once_broken
    _send(rt, BATCH * 3)
    rt.flush_device()
    assert bridge.guard.failures == 1 and bridge.guard.fallback_events == BATCH
    trackers = bridge.probe.phases.trackers
    for cpu, wall in COMPANIONS.items():
        if cpu not in ("route_cpu", "key_lookup_cpu"):
            assert trackers[cpu].count == trackers[wall].count == 2 * BATCH, cpu
    assert trackers["driver_cpu"].count == 2 * BATCH
    rep = rt.observability.latency_report()["queries"]["agg"]
    assert rep["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-5)


# ---------------------------------------------------------------------------
# publish, split where the code splits
# ---------------------------------------------------------------------------

def _takes_columns(rt):
    rt.add_rows_callback("O", lambda cols, ts, n: None)


def _takes_events(rt):
    rt.add_callback("O", StreamCallback(lambda evs: None))


def _takes_events_and_a_query_callback(rt):
    _takes_events(rt)
    rt.add_query_callback("agg", QueryCallback(lambda ts, cur, exp: None))


@pytest.mark.parametrize("subscribe, builds", [
    (_takes_columns, False),
    # its receiver takes the columns and builds the Event list it asked for
    (_takes_events, True),
    # the chunk goes out as events: rows and StreamEvents in the bridge
    (_takes_events_and_a_query_callback, True),
], ids=["rows-callback", "stream-callback", "query-callback"])
def test_publish_build_is_what_the_engine_builds_for_who_takes_events(
        manager, subscribe, builds):
    rt, bridge = _runtime(manager, True, subscribe=subscribe)
    _send(rt, BATCH * 4)
    rt.flush_device()
    shape = "events" if subscribe is _takes_events_and_a_query_callback \
        else "columns"
    assert bridge.egress[shape][0] == 4
    trackers = bridge.probe.phases.trackers
    assert trackers["publish_build"].count \
        == trackers["sink_publish"].count == 4 * BATCH
    built = trackers["publish_build"].hist.sum
    assert (built > 0.0) == builds
    # a part of the publishing, never more than it
    assert built <= trackers["sink_publish"].hist.sum
    rep = rt.observability.latency_report()["queries"]["agg"]
    assert "publish_build" in rep["phases"]
    assert rep["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-5)


def test_the_build_is_timed_apart_from_the_users_function(manager):
    """A subscriber's own function that sleeps is the rest of
    ``sink_publish``, not what the engine built for it."""
    slept = 0.03
    rt, bridge = _runtime(manager, True, subscribe=lambda r: r.add_callback(
        "O", StreamCallback(lambda evs: time.sleep(slept))))
    _send(rt, BATCH * 2)
    rt.flush_device()
    trackers = bridge.probe.phases.trackers
    publish = trackers["sink_publish"].hist.sum / (2 * BATCH)
    build = trackers["publish_build"].hist.sum / (2 * BATCH)
    cpu = trackers["sink_publish_cpu"].hist.sum / (2 * BATCH)
    assert publish >= slept
    assert 0.0 < build < publish - slept * 0.9
    assert cpu < publish - slept * 0.9      # the sleep is off the CPU


# ---------------------------------------------------------------------------
# nothing an event
# ---------------------------------------------------------------------------

def test_no_clock_is_read_on_a_per_event_path():
    from siddhi_tpu.core.device_bridge import DeviceQueryBridge
    from siddhi_tpu.core.stream import InputHandler, StreamJunction
    from siddhi_tpu.tpu.batch import BatchBuilder
    from siddhi_tpu.tpu.nfa import MergedBatchBuilder
    from siddhi_tpu.tpu.partition import LaneBatchBuilder
    paths = [InputHandler.send, StreamJunction.send_event,
             DeviceQueryBridge.on_event]
    for builder in (BatchBuilder, MergedBatchBuilder, LaneBatchBuilder):
        for name in ("append", "append_many", "append_columns"):
            fn = vars(builder).get(name)
            if fn is not None:
                paths.append(fn)
    assert len(paths) >= 6
    for fn in paths:
        assert "thread_time" not in inspect.getsource(fn), fn.__qualname__


def test_thread_time_is_the_calling_threads_own_clock():
    """What the companions rest on: the clock stands still while its thread
    sleeps and while ANOTHER thread spins."""
    spun = []
    other = threading.Thread(target=lambda: spun.append(_spin(0.03)))
    c0, t0 = time.thread_time(), time.perf_counter()
    other.start()
    other.join(timeout=60.0)
    time.sleep(0.02)
    cpu, wall = time.thread_time() - c0, time.perf_counter() - t0
    assert spun and wall >= 0.05 and cpu < wall / 2
