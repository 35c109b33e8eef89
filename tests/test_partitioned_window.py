"""A keyed sliding ``window.length(N)`` served from the chip (ISSUE 39): the
device branch of ``partition with (deviceID of TempStream)`` round one
windowed aggregate query, one ``DeviceQueryBridge`` of kind ``'partition'``
over a ``KeyedWindowRuntime``, held against the scalar interpreter's
per-key ``PartitionRuntime`` and the benchmark's plain reference on the same
seeded events.

CPU, small sizes: batches of 64-256 events, tables of 8-512 keys.
"""

from __future__ import annotations

import hashlib
import logging
import os
import sys

import numpy as np
import pytest

from siddhi_tpu import InMemoryPersistenceStore, SiddhiManager, StreamCallback
from siddhi_tpu.tpu.expr_compile import DeviceCompileError
from siddhi_tpu.tpu.keyed_window import KeyDirectory, KeyedWindowRuntime
from util_parity import assert_rows_match

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEAD = "define stream TempStream (deviceID long, roomNo int, temp double);\n"
QUERY = ("from TempStream{filter}#window.length({length})\n"
         "select roomNo, deviceID, {aggs}\n{having}"
         "insert into DeviceTempStream;")
APP = HEAD + ("partition with (deviceID of TempStream) begin\n{device}\n"
              + QUERY + "\nend;\n")
DEVICE = "@device(strict='true', async='{a}', batch='{b}', keys='{k}')"


def _app(device="", aggs="max(temp) as maxTemp", having="", length=10,
         filter_="", app_head="") -> str:
    return app_head + APP.format(device=device, aggs=aggs, length=length,
                                 filter=filter_, having=having)


def _device(batch=128, keys=512, async_="false") -> str:
    return DEVICE.format(a=async_, b=batch, k=keys)


def _events(n: int, keys: int, seed: int = 3, sparse: bool = False):
    """``n`` readings of ``keys`` devices, Zipf; ``sparse``: the ids are
    63-bit numbers far apart, so nothing can index by the id's value."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, keys + 1, dtype=np.float64) ** -0.8
    k = rng.choice(keys, size=n, p=p / p.sum()).astype(np.int64)
    dev = (k * 1_000_000_007 + (1 << 62) + 12345) if sparse else k
    room = rng.integers(0, 1000, n).astype(np.int32)
    temp = np.round(rng.uniform(0.0, 100.0, n), 3)
    return dev.astype(np.int64), room, temp


def _run(text, dev, room, temp, columns=True, chunk=100, manager=None,
         start=0, restore=False):
    """(runtime, manager, rows); the manager is left running."""
    m = manager or SiddhiManager()
    rows: list = []
    rt = m.create_siddhi_app_runtime(text, playback=True)
    rt.add_callback("DeviceTempStream", StreamCallback(
        lambda evs: rows.extend(list(e.data) for e in evs)))
    rt.start()
    if restore:
        rt.restore_last_revision()
    _send(rt, dev, room, temp, columns, chunk, start)
    rt.flush_device()
    return rt, m, rows


def _send(rt, dev, room, temp, columns, chunk=100, start=0):
    h = rt.input_handler("TempStream")
    n = len(temp)
    if columns:
        for i in range(0, n, chunk):
            j = min(i + chunk, n)
            h.send_columns({"deviceID": dev[i:j], "roomNo": room[i:j],
                            "temp": temp[i:j]},
                           np.arange(1000 + start + i, 1000 + start + j,
                                     dtype=np.int64))
    else:
        for i in range(n):
            h.send([int(dev[i]), int(room[i]), float(temp[i])],
                   timestamp=1000 + start + i)


def _interpreter(dev, room, temp, **app) -> list:
    rt, m, rows = _run(_app(**app), dev, room, temp, columns=False)
    try:
        assert not rt.device_bridges and len(rt.partition_runtimes) == 1
    finally:
        m.shutdown()
    return rows


def _served(dev, room, temp, device=None, columns=True, chunk=100, **app):
    rt, m, rows = _run(_app(device or _device(), **app), dev, room, temp,
                       columns=columns, chunk=chunk)
    try:
        assert len(rt.device_bridges) == 1
        assert not (rt.partition_runtimes or rt.host_bridges
                    or rt.fleet_bridges or rt.query_runtimes)
        bridge = rt.device_bridges[0]
        assert bridge.kind == "partition"
        assert isinstance(bridge.runtime, KeyedWindowRuntime)
        assert bridge.guard.report()["failures"] == 0
        assert bridge.probe.events == len(temp)
        return rows, bridge.runtime
    finally:
        m.shutdown()


def _reference_module():
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        from harness.manifest import load_module
    finally:
        sys.path.pop(0)
    return load_module(os.path.join(REPO, "benchmark", "configs",
                                    "partitioned-window.py"),
                       "test_partitioned_window_reference")


# ---------------------------------------------------------------------------
# the served path against the interpreter and the plain reference
# ---------------------------------------------------------------------------

AGGS = {
    "max": "max(temp) as maxTemp",
    "min": "min(temp) as minTemp",
    "avg": "avg(temp) as avgTemp",
    "count": "count() as n",
    "all": "max(temp) as maxTemp, min(temp) as lo, avg(temp) as av, "
           "sum(roomNo) as rooms, count() as n",
}


@pytest.mark.parametrize("columns", [True, False], ids=["columns", "send"])
@pytest.mark.parametrize("agg", list(AGGS))
def test_served_rows_equal_the_interpreters(agg, columns):
    dev, room, temp = _events(1500, keys=40, seed=5)
    want = _interpreter(dev, room, temp, aggs=AGGS[agg])
    assert len(want) == 1500                # no having: a row an event
    got, _ = _served(dev, room, temp, columns=columns, aggs=AGGS[agg])
    assert_rows_match(want, got)


@pytest.mark.parametrize("having, rows", [
    ("having maxTemp > 90.0\n", "some"),
    ("having maxTemp > 100.0\n", "none"),
])
def test_having_that_passes_and_that_fails(having, rows):
    dev, room, temp = _events(2000, keys=50, seed=7)
    want = _interpreter(dev, room, temp, having=having)
    assert (len(want) > 100) if rows == "some" else want == []
    got, _ = _served(dev, room, temp, having=having)
    assert_rows_match(want, got)


def test_rows_equal_the_plain_references_on_sparse_63_bit_ids():
    """The benchmark's reference (NumPy, no kernel) on the same events, the
    cell's query: 63-bit ids far apart, batches that cut keys' windows."""
    ref = _reference_module()
    dev, room, temp = _events(6000, keys=300, seed=11, sparse=True)
    assert dev.min() > 1 << 62
    config = {"window_length": 10, "having_above": 95.0}
    want = ref.reference(config, {"deviceID": dev, "roomNo": room,
                                  "temp": temp}, len(temp))
    got, rt = _served(dev, room, temp, device=_device(batch=256, keys=512),
                      having="having maxTemp > 95.0\n")
    assert len(got) == len(want["last_event"]) > 300
    expect = sorted(zip(want["columns"]["roomNo"].tolist(),
                        want["columns"]["deviceID"].tolist(),
                        want["columns"]["maxTemp"].tolist()))
    assert sorted((r, d, float(np.float32(t))) for r, d, t in got) == expect
    assert rt.step_gauges["keyed_live_keys"] == len(np.unique(dev))


def test_keys_with_fewer_than_a_window_of_events():
    """300 keys over 600 events: most keys hold two or three readings."""
    dev, room, temp = _events(600, keys=300, seed=13)
    counts = np.bincount(np.unique(dev, return_inverse=True)[1])
    assert (counts < 10).mean() > 0.9
    want = _interpreter(dev, room, temp, aggs=AGGS["all"])
    got, _ = _served(dev, room, temp, aggs=AGGS["all"])
    assert_rows_match(want, got)


def test_a_key_with_more_than_a_window_inside_one_batch():
    """Three keys, batches of 64: each key has about 20 events a batch, so
    its window turns over inside a batch and is carried across the next."""
    dev, room, temp = _events(640, keys=3, seed=17)
    want = _interpreter(dev, room, temp, aggs=AGGS["all"])
    got, _ = _served(dev, room, temp, device=_device(batch=64, keys=8),
                     aggs=AGGS["all"], chunk=64)
    assert_rows_match(want, got)


def test_one_key_fills_whole_batches():
    n = 512
    rng = np.random.default_rng(19)
    dev = np.full(n, 7, np.int64)
    room = rng.integers(0, 1000, n).astype(np.int32)
    temp = np.round(rng.uniform(0.0, 100.0, n), 3)
    want = _interpreter(dev, room, temp, aggs=AGGS["all"], length=5)
    got, rt = _served(dev, room, temp, device=_device(batch=64, keys=8),
                      aggs=AGGS["all"], length=5)
    assert_rows_match(want, got)
    assert rt.step_gauges["keyed_live_keys"] == 1


def test_a_filter_before_the_window():
    dev, room, temp = _events(1500, keys=30, seed=23)
    app = dict(filter_="[temp > 40.0]", aggs=AGGS["all"])
    want = _interpreter(dev, room, temp, **app)
    got, _ = _served(dev, room, temp, **app)
    assert_rows_match(want, got)


def test_a_key_past_the_tables_capacity_is_counted_in_drops(caplog):
    """A table of 8 rows and 20 keys: the first 8 keys to arrive keep their
    windows and rows; every event of the other 12 is counted in ``drops``
    (a state counter: the benchmark fails such a run) and warned of."""
    dev, room, temp = _events(1200, keys=20, seed=29)
    order = np.unique(dev, return_index=True)
    first8 = order[0][np.argsort(order[1])][:8]
    held = np.isin(dev, first8)
    want = [r for r in _interpreter(dev, room, temp) if r[1] in first8]
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu"):
        got, rt = _served(dev, room, temp, device=_device(batch=64, keys=8))
    assert_rows_match(want, got)
    assert rt.drop_count == int((~held).sum()) > 0
    assert rt.step_gauges["key_table_fill_share"] == 1.0
    assert any("capacity" in r.getMessage() for r in caplog.records)


def test_the_directory_gives_stable_slots_in_order_of_arrival():
    d = KeyDirectory(4)
    big = np.array([7 << 60, 5, -3, 5, 7 << 60], np.int64)
    assert d.slots_of(big).tolist() == [0, 1, 2, 1, 0]
    assert d.slots_of(np.array([-3, 77, 78, 5], np.int64)).tolist() \
        == [2, 3, 4, 1]                       # 78 finds no room: capacity
    assert len(d) == 4
    other = KeyDirectory(4)
    other.restore(d.snapshot())
    assert other.slots_of(np.array([77, 7 << 60], np.int64)).tolist() \
        == [3, 0]


@pytest.mark.parametrize("capacity, ids", [(64, 50), (500, 2000),
                                           (4096, 3000)])
def test_the_directory_agrees_with_a_dict(capacity, ids):
    """Batches of sparse 64-bit ids (negative ones too) against a dict that
    hands out slots in order of first arrival: the table's probes, a full
    table and ids past its capacity, and a restored copy."""
    rng = np.random.default_rng(capacity)
    pool = rng.integers(-(1 << 63), (1 << 63) - 1, ids, dtype=np.int64)
    d, want = KeyDirectory(capacity), {}
    for i in range(12):
        keys = pool[rng.integers(0, ids, 257)]
        for k in keys.tolist():
            if k not in want and len(want) < capacity:
                want[k] = len(want)
        assert d.slots_of(keys).tolist() == [want.get(k, capacity)
                                             for k in keys.tolist()]
        if i == 6:
            d2 = KeyDirectory(capacity)
            d2.restore(d.snapshot())
            d = d2
    assert len(d) == len(want)


# ---------------------------------------------------------------------------
# snapshot / restore
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_", ["false", "true"])
def test_snapshot_then_restore_mid_stream_equals_an_uninterrupted_run(
        async_):
    dev, room, temp = _events(2400, keys=60, seed=31, sparse=True)
    text = "@app:name('KeyedSnap')\n" + _app(
        _device(batch=128, async_=async_), aggs=AGGS["all"])
    rt0, m0, whole = _run(text, dev, room, temp)
    m0.shutdown()
    cut = 1111                                  # mid-batch, mid-chunk
    store = InMemoryPersistenceStore()
    m1 = SiddhiManager()
    m1.set_persistence_store(store)
    rt1, _, first = _run(text, dev[:cut], room[:cut], temp[:cut], manager=m1)
    rt1.persist()
    m1.shutdown()
    m2 = SiddhiManager()
    m2.set_persistence_store(store)
    try:
        rt2, _, rest = _run(text, dev[cut:], room[cut:], temp[cut:],
                            manager=m2, start=cut, restore=True)
        assert len(rest) == 2400 - cut
        assert_rows_match(whole, first + rest)
        assert rt2.device_bridges[0].runtime.step_gauges[
            "keyed_live_keys"] == len(np.unique(dev))
    finally:
        m2.shutdown()


# ---------------------------------------------------------------------------
# the keys are looked up where the batch is sealed
# ---------------------------------------------------------------------------

def _deployed(device, **app):
    """(runtime, manager, rows, keyed runtime) of a started keyed app."""
    m = SiddhiManager()
    rows: list = []
    rt = m.create_siddhi_app_runtime(_app(device, **app), playback=True)
    rt.add_callback("DeviceTempStream", StreamCallback(
        lambda evs: rows.extend(list(e.data) for e in evs)))
    rt.start()
    return rt, m, rows, rt.device_bridges[0].runtime


def _tap_seals(r) -> list:
    """Every batch the runtime seals, as its flush hands it on."""
    sealed, inner = [], r._emit_batch

    def emit():
        sealed.append(inner())
        return sealed[-1]

    r._emit_batch = emit
    return sealed


@pytest.mark.parametrize("async_", ["false", "true"])
def test_a_sealed_batch_carries_the_slots_the_directory_gives(async_):
    """Batches of 64 over 20 keys and a table of 8: each sealed batch's
    ``slot`` is what a fresh directory fed the same batches gives, a key
    admitted in order of first appearance and every key past the table's
    8 given the capacity; the padding of a partial batch reads 0."""
    dev, room, temp = _events(500, keys=20, seed=41, sparse=True)
    rt, m, _rows, r = _deployed(_device(batch=64, keys=8, async_=async_))
    sealed = _tap_seals(r)
    try:
        _send(rt, dev, room, temp, columns=True, chunk=50)
        rt.flush_device()
    finally:
        m.shutdown()
    assert [b["count"] for b in sealed] == [64] * 7 + [52]
    twin, first = KeyDirectory(8), {}
    for b in sealed:
        n, slot = b["count"], b["slot"]
        assert slot.dtype == np.int32 and slot.shape == (64,)
        keys = b["cols"]["deviceID"][:n]
        assert slot[:n].tolist() == twin.slots_of(keys).tolist()
        for k in keys.tolist():
            if k not in first and len(first) < 8:
                first[k] = len(first)
        assert slot[:n].tolist() == [first.get(k, 8) for k in keys.tolist()]
        assert not slot[n:].any()
    assert (np.concatenate([b["slot"][:b["count"]] for b in sealed])
            == 8).sum() == int((~np.isin(dev, list(first))).sum()) > 0


def test_dispatch_makes_no_directory_call():
    """An async keyed window: every directory call is made by the thread
    that sends, inside the seal, and none inside ``dispatch`` (the driver's
    thread launches the step and nothing else)."""
    import inspect
    import threading
    dev, room, temp = _events(700, keys=30, seed=43)
    rt, m, rows, r = _deployed(_device(batch=64, async_="true"))
    calls, inner = [], r.directory.slots_of

    def slots_of(keys):
        calls.append((threading.get_ident(),
                      {f.function for f in inspect.stack()}))
        return inner(keys)

    r.directory.slots_of = slots_of
    dispatched = []
    guarded = r.dispatch

    def dispatch(batch):
        dispatched.append(threading.get_ident())
        return guarded(batch)

    r.dispatch = dispatch
    try:
        _send(rt, dev, room, temp, columns=True, chunk=64)
        rt.flush_device()
    finally:
        m.shutdown()
    assert len(calls) == len(dispatched) == 11       # ceil(700 / 64)
    me = threading.get_ident()
    assert {t for t, _ in calls} == {me}
    assert set(dispatched) == {r.driver._thread.ident} != {me}
    for _, stack in calls:
        assert "_sealing" in stack and "dispatch" not in stack
    assert len(rows) == 700
    assert r.step_gauges["keyed_live_keys"] == len(np.unique(dev))


def _interrupted(dev, room, temp, cut, staged):
    """An async keyed window paused after ``cut`` events with ``staged``
    more sent (sealed batches left in the ring, the rest in the builder),
    captured as they stand and restored into a second runtime that takes
    the rest: the rows of both."""
    head = "@app:name('KeyedStaged')\n"
    device = _device(batch=64, async_="true")
    rt1, m1, first, _ = _deployed(device, app_head=head, aggs=AGGS["all"])
    try:
        _send(rt1, dev[:cut], room[:cut], temp[:cut], columns=True)
        rt1.flush_device()
        driver = rt1.device_bridges[0].driver
        driver.pause()
        try:
            _send(rt1, dev[cut:staged], room[cut:staged], temp[cut:staged],
                  columns=False, start=cut)
            assert len(driver.snapshot_staged()) == (staged - cut) // 64
            assert len(rt1.device_bridges[0].runtime.builder) \
                == (staged - cut) % 64 > 0
            blob = rt1.snapshot_service.full_snapshot()
            before = list(first)
        finally:
            driver.resume()
    finally:
        m1.shutdown()
    rt2, m2, rest, _ = _deployed(device, app_head=head, aggs=AGGS["all"])
    try:
        rt2.restore(blob)
        _send(rt2, dev[staged:], room[staged:], temp[staged:], columns=True,
              start=staged)
        rt2.flush_device()
    finally:
        m2.shutdown()
    return before + rest


@pytest.mark.parametrize("path", ["send", "flush_sync", "restore"])
def test_every_way_a_batch_is_sealed_gives_the_interpreters_rows(path):
    """The per-event ``send`` (its capacity flushes seal on the sending
    thread), a ``flush_sync`` after every odd-sized chunk (every batch a
    partial one, sealed by the flushing thread), and a snapshot taken with
    sealed batches in the ring and a partly staged batch, restored into a
    second runtime: the rows are the scalar interpreter's."""
    dev, room, temp = _events(1300, keys=70, seed=47, sparse=True)
    want = _interpreter(dev, room, temp, aggs=AGGS["all"])
    if path == "restore":
        got = _interrupted(dev, room, temp, cut=300, staged=300 + 2 * 64 + 37)
        assert_rows_match(want, got)
        return
    rt, m, got, r = _deployed(_device(batch=64, async_="true"),
                              aggs=AGGS["all"])
    sealed = _tap_seals(r)
    try:
        if path == "send":
            _send(rt, dev, room, temp, columns=False)
        else:
            for i in range(0, len(temp), 45):
                j = min(i + 45, len(temp))
                _send(rt, dev[i:j], room[i:j], temp[i:j], columns=True,
                      start=i)
                rt.flush_device()
        rt.flush_device()
    finally:
        m.shutdown()
    assert_rows_match(want, got)
    assert all("slot" in b for b in sealed) and len(sealed) == (
        21 if path == "send" else 29)


def test_the_key_lookup_is_recorded_once_a_batch_inside_pack(monkeypatch):
    """The tracker ``key_lookup`` (and its CPU clock) once a batch, inside
    the batch's ``pack`` as ``phases.NESTED`` says, its span inside
    ``siddhi:seal.pack`` on the sealing thread; the serial sum still
    reconciles."""
    import contextlib
    import threading
    from siddhi_tpu.observability.phases import NESTED
    from siddhi_tpu.tpu import step_runtime
    assert NESTED["key_lookup"] == "pack"
    opened = []

    @contextlib.contextmanager
    def span(name):
        opened.append(("open", name, threading.get_ident()))
        yield
        opened.append(("close", name, threading.get_ident()))

    monkeypatch.setattr(step_runtime, "span", span)
    dev, room, temp = _events(640, keys=30, seed=53)
    rt, m, rows, r = _deployed(_device(batch=64, async_="true"))
    bridge = rt.device_bridges[0]
    records, record = [], bridge.probe.phases.record_batch

    def record_batch(n, **kw):
        records.append(kw)
        record(n, **kw)

    bridge.probe.phases.record_batch = record_batch
    try:
        _send(rt, dev, room, temp, columns=True, chunk=64)
        rt.flush_device()
        rep = rt.observability.latency_report()["queries"][bridge.query_name]
    finally:
        m.shutdown()
    assert len(records) == bridge.probe.steps == 10
    for kw in records:
        assert 0.0 < kw["parts"]["key_lookup"] <= kw["pack_s"]
        assert kw["parts"]["key_lookup_cpu"] is not None
    trackers = bridge.probe.phases.trackers
    assert trackers["key_lookup"].count == trackers["key_lookup_cpu"].count \
        == trackers["pack"].count == 640
    assert trackers["key_lookup"].hist.sum <= trackers["pack"].hist.sum
    assert rep["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-5)
    q = bridge.query_name
    me = threading.get_ident()
    lookups = [i for i, (what, name, _) in enumerate(opened)
               if what == "open" and name == f"siddhi:seal.key_lookup:{q}"]
    assert len(lookups) == 10
    for i in lookups:
        assert opened[i - 1] == ("open", f"siddhi:seal.pack:{q}", me)
        assert opened[i + 1] == ("close", f"siddhi:seal.key_lookup:{q}", me)
        assert opened[i + 2] == ("close", f"siddhi:seal.pack:{q}", me)
    assert not any("dispatch.key_lookup" in name for _, name, _ in opened)


# ---------------------------------------------------------------------------
# what does not lower
# ---------------------------------------------------------------------------

KEYED = HEAD + "partition with (deviceID of TempStream) begin\n" \
    "@device(strict='{strict}', batch='64', keys='64')\n{query}\nend;\n"
REFUSED = {
    "time-window": ("from TempStream#window.time(10 sec) select deviceID, "
                    "max(temp) as m insert into O;", "window.length"),
    "no-window": ("from TempStream select deviceID, max(temp) as m "
                  "insert into O;", "window.length"),
    "group-by": ("from TempStream#window.length(10) select roomNo, "
                 "sum(temp) as s group by roomNo insert into O;",
                 "group-by"),
    "no-aggregate": ("from TempStream#window.length(10) select deviceID, "
                     "temp insert into O;", "no aggregate"),
    "too-long": ("from TempStream#window.length(500) select deviceID, "
                 "max(temp) as m insert into O;", r"length\(500\)"),
    "output-rate": ("from TempStream#window.length(10) select deviceID, "
                    "max(temp) as m output last every 5 events "
                    "insert into O;", "rate limiting"),
    "two-queries": ("from TempStream#window.length(10) select deviceID, "
                    "max(temp) as m insert into O;\n"
                    "from TempStream select deviceID insert into P;",
                    "several queries"),
    "sequence": ("from every e1=TempStream[temp > 50.0], "
                 "e2=TempStream[temp > e1.temp] select e1.temp as a, "
                 "e2.temp as b insert into O;", "per-key strictness"),
}


@pytest.mark.parametrize("name", list(REFUSED))
def test_what_does_not_lower_raises_under_strict_and_keeps_the_host_tiers(
        name):
    query, why = REFUSED[name]
    m = SiddhiManager()
    try:
        with pytest.raises(DeviceCompileError, match=why):
            m.create_siddhi_app_runtime(
                KEYED.format(strict="true", query=query), playback=True)
        rt = m.create_siddhi_app_runtime(
            KEYED.format(strict="false", query=query), playback=True)
        assert not rt.device_bridges and len(rt.partition_runtimes) == 1
    finally:
        m.shutdown()


@pytest.mark.parametrize("text, why", [
    ("define stream S (k double, v double);\n"
     "partition with (k of S) begin\n@device(strict='true')\n"
     "from S#window.length(10) select max(v) as m insert into O;\nend;\n",
     "DOUBLE attribute"),
    ("define stream S (k int, v double);\n"
     "partition with (k < 10 as 'low' or k >= 10 as 'high' of S) begin\n"
     "@device(strict='true')\n"
     "from S#window.length(10) select max(v) as m insert into O;\nend;\n",
     "range/expression"),
], ids=["float-key", "range-partition"])
def test_a_float_key_and_a_range_partition_are_refused(text, why):
    m = SiddhiManager()
    try:
        with pytest.raises(DeviceCompileError, match=why):
            m.create_siddhi_app_runtime(text, playback=True)
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# the programs the benchmark's other configurations compile
# ---------------------------------------------------------------------------

# sha256 of the lowered text (no locations, so no names of scopes either:
# what the persistent compile cache keys a program by) of each existing
# configuration's one jitted step at its benchmark sizes, on this backend,
# as the parent of PR 39 lowers them. A program that changes misses the
# compile cache on the chip, and `setup_s` jumps (PR 38's first draft:
# 35.8 against 19.7 s). The two blocked-NFA programs (`pattern-chain8`,
# `partitioned-chain`) are pinned as they lower since their stage grids
# test `within` as an int32 delta against a per-candidate limit
LOWERED = {
    "pattern-chain8":
        "e8387c145d7f36c8",
    "window-groupby":
        "3415b13afb44a26f",
    "partitioned-chain":
        "5781f06553a2f236",
    "partitioned-kleene":
        "387d916cb49ba53c",
    "nexmark-q5":
        "5667c34de7268a0d",
}


@pytest.mark.parametrize("name", list(LOWERED))
def test_the_benchmarks_other_programs_lower_as_they_did(name):
    path = os.path.join(REPO, "benchmark", "configs", name + ".siddhi")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    m = SiddhiManager()
    try:
        r = m.create_siddhi_app_runtime(
            text, playback=True).device_bridges[0].runtime
        b = r.builder.emit()
        if hasattr(r, "vstep"):             # a served partition's lanes
            low = r.vstep.lower(r.state, *r._lay_out(b))
        elif hasattr(r, "compiler"):        # the single-lane NFA
            low = r.compiler._step.lower(
                r.state, b["cols"], b["tag"], b["ts"], b["ts_base"],
                np.int32(b["count"]))
        else:
            low = r.compiled._step.lower(r.state, b["cols"], b["ts"],
                                         b["valid"])
        digest = hashlib.sha256(low.as_text().encode()).hexdigest()
    finally:
        m.shutdown()
    assert digest.startswith(LOWERED[name]), digest


# the same for the keyed step of `partitioned-window`, over the slot column
# its seal hands the step: the lookup's move to the seal left the program
# as it was, so the chip's compile cache still holds it
KEYED_LOWERED = "0e93038486f6b4a6"


def test_the_keyed_step_lowers_as_it_did():
    path = os.path.join(REPO, "benchmark", "configs",
                        "partitioned-window.siddhi")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    m = SiddhiManager()
    try:
        r = m.create_siddhi_app_runtime(
            text, playback=True).device_bridges[0].runtime
        b = r._emit_batch()
        assert b["slot"].dtype == np.int32
        low = r.compiled.step.lower(r.state, b["cols"], b["ts"], b["valid"],
                                    b["slot"])
        digest = hashlib.sha256(low.as_text().encode()).hexdigest()
    finally:
        m.shutdown()
    assert digest.startswith(KEYED_LOWERED), digest
