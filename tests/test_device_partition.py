"""The device branch of a ``partition with`` block (ISSUE 29): one served
``DeviceQueryBridge`` of kind ``'partition'`` over a lane-stacked
``PartitionedNFARuntime``, held row for row (as multisets) against the
scalar interpreter's per-key ``PartitionRuntime`` on the same events.

CPU, small sizes: 4-8 lanes, batches of 64-256 events, 3-state chains.
"""

from __future__ import annotations

import logging
import zlib

import numpy as np
import pytest

from siddhi_tpu import InMemoryPersistenceStore, SiddhiManager, StreamCallback
from siddhi_tpu.core.columns import ColumnsOut
from siddhi_tpu.tpu import partition as tpu_partition
from siddhi_tpu.tpu.expr_compile import DeviceCompileError
from siddhi_tpu.tpu.partition import (
    LaneBatchBuilder,
    PartitionedNFARuntime,
    lane_capacity_for,
)
from util_parity import assert_same_chunk

CHAIN = ("from every e1=S[v > 50.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v] "
         "within {within}\n"
         "select e1.v as v1, e2.v as v2, e3.v as v3 insert into Alerts;")
APP = ("{head}define stream S (dev string, v double);\n"
       "partition with (dev of S) begin\n{device}\n" + CHAIN + "\nend;\n")
DEVICE = "@device(strict='true', async='{a}', batch='{b}', slots='{s}', " \
         "lanes='{p}')"


def _app(device: str = "", within: int = 4000, head: str = "") -> str:
    return APP.format(head=head, device=device, within=within)


def _device(batch=128, slots=256, lanes=4, async_="false") -> str:
    return DEVICE.format(a=async_, b=batch, s=slots, p=lanes)


def _events(n: int, keys: int, seed: int = 3, zipf: bool = False):
    rng = np.random.default_rng(seed)
    if zipf:
        p = np.arange(1, keys + 1, dtype=np.float64) ** -1.1
        k = rng.choice(keys, size=n, p=p / p.sum())
    else:
        k = rng.integers(0, keys, n)
    devs = np.array([f"dev{i}" for i in range(keys)], dtype=object)[k]
    return devs, np.round(rng.uniform(0.0, 100.0, n), 3)


def _f32(rows) -> list:
    """Rows as a sorted multiset, DOUBLE through float32 as the device
    computes it (three decimals stay distinct there)."""
    return sorted(tuple(float(np.float32(x)) for x in r) for r in rows)


def _run(app_text: str, devs, vs, columns: bool = False, chunk: int = 96,
         manager: SiddhiManager | None = None, base_ts: int = 1000):
    m = manager or SiddhiManager()
    rows: list = []
    rt = m.create_siddhi_app_runtime(app_text, playback=True)
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: rows.extend(tuple(e.data) for e in evs)))
    rt.start()
    _send(rt, devs, vs, columns, chunk, base_ts)
    rt.flush_device()
    return rt, m, rows


def _send(rt, devs, vs, columns, chunk=96, base_ts=1000, start=0):
    handler = rt.input_handler("S")
    n = len(vs)
    if columns:
        for i in range(0, n, chunk):
            j = min(i + chunk, n)
            handler.send_columns(
                {"dev": devs[i:j], "v": vs[i:j]},
                np.arange(base_ts + start + i, base_ts + start + j,
                          dtype=np.int64))
    else:
        for i in range(n):
            handler.send([devs[i], float(vs[i])],
                         timestamp=base_ts + start + i)


def _interpreter(devs, vs, within: int = 4000) -> list:
    rt, m, rows = _run(_app(within=within), devs, vs)
    assert not rt.device_bridges and len(rt.partition_runtimes) == 1
    m.shutdown()
    return _f32(rows)


# ---------------------------------------------------------------------------
# the served path against the scalar interpreter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("columns", [False, True], ids=["send", "columns"])
@pytest.mark.parametrize("zipf", [False, True], ids=["uniform", "zipf"])
def test_served_partition_rows_equal_the_interpreters(columns, zipf):
    devs, vs = _events(3000, keys=40, zipf=zipf)
    want = _interpreter(devs, vs)
    assert len(want) > 100
    rt, m, rows = _run(_app(_device()), devs, vs, columns=columns)
    try:
        assert _f32(rows) == want
        # exactly one served bridge, nothing of the host tiers beside it
        assert len(rt.device_bridges) == 1
        assert not (rt.partition_runtimes or rt.host_bridges
                    or rt.fleet_bridges or rt.query_runtimes)
        bridge = rt.device_bridges[0]
        assert bridge.kind == "partition"
        assert isinstance(bridge.runtime, PartitionedNFARuntime)
        assert bridge.probe is not None and bridge.guard is not None
        assert bridge.probe.events == 3000
        assert bridge.guard.report()["failures"] == 0
        assert bridge.runtime.lane_gauges["drops"] == 0
        assert 0.0 < bridge.runtime.lane_gauges["fullest_table_share"] < 1.0
    finally:
        m.shutdown()


def test_async_bridge_has_driver_probe_and_guard_and_agrees():
    devs, vs = _events(2000, keys=24, seed=9)
    want = _interpreter(devs, vs)
    rt, m, rows = _run(_app(_device(async_="true")), devs, vs, columns=True)
    try:
        bridge = rt.device_bridges[0]
        assert bridge.driver is not None and bridge.driver.window == 2
        assert bridge.runtime.driver is bridge.driver
        assert _f32(rows) == want
        assert bridge.probe.events == 2000
        assert bridge.driver.batches_stepped == bridge.probe.steps
    finally:
        m.shutdown()


@pytest.mark.parametrize("keys, lanes", [(64, 4), (3, 8)],
                         ids=["more-keys-than-lanes", "fewer-keys"])
def test_keys_against_lanes(keys, lanes):
    devs, vs = _events(1500, keys=keys, seed=5)
    want = _interpreter(devs, vs)
    assert want
    rt, m, rows = _run(_app(_device(batch=96, lanes=lanes, slots=512)),
                       devs, vs, columns=True, chunk=50)
    try:
        assert _f32(rows) == want
        assert rt.device_bridges[0].runtime.lane_gauges["drops"] == 0
    finally:
        m.shutdown()


@pytest.mark.parametrize("columns", [False, True], ids=["send", "columns"])
def test_a_full_lane_seals_the_batch_and_nothing_is_lost(columns):
    """One hot key takes 70 % of the events: its lane (128 events of a
    batch of 256 over 8 lanes) fills before the batch does, the chunk is
    split there and the rest opens the next batch; the hot key's events
    keep their order (the rows equal the interpreter's, and a chain is
    order-sensitive)."""
    n = 1200
    rng = np.random.default_rng(21)
    hot = rng.uniform(size=n) < 0.7
    devs = np.where(hot, "hot", np.array(
        [f"dev{k}" for k in rng.integers(0, 30, n)], dtype=object))
    devs = devs.astype(object)
    vs = np.round(rng.uniform(0.0, 100.0, n), 3)
    want = _interpreter(devs, vs)
    # batch 256 over 8 lanes: lane capacity 128 (2.5 x 32 -> 80 -> 128)
    assert lane_capacity_for(256, 8) == 128
    rt, m, rows = _run(_app(_device(batch=256, lanes=8, slots=512)), devs,
                       vs, columns=columns, chunk=200)
    try:
        bridge = rt.device_bridges[0]
        causes = bridge.probe.flush_causes
        assert causes.get("lane_full", 0) >= 4, causes
        assert bridge.probe.events == n            # nothing dropped
        assert _f32(rows) == want                  # nothing reordered
        assert bridge.runtime.lane_gauges["fullest_lane_events"] == 128
        assert bridge.probe.steps == sum(causes.values())
    finally:
        m.shutdown()


def test_within_expires_across_batches():
    devs, vs = _events(2500, keys=10, seed=13)
    want = _interpreter(devs, vs, within=90)
    loose = _interpreter(devs, vs, within=4000)
    assert 0 < len(want) < len(loose)      # the window does cut matches
    rt, m, rows = _run(_app(_device(batch=64, lanes=4), within=90), devs, vs,
                       columns=True, chunk=64)
    try:
        assert _f32(rows) == want
    finally:
        m.shutdown()


def test_lane_capacity_rule():
    assert lane_capacity_for(32768, 256) == 320
    assert lane_capacity_for(2048, 8) == 640
    assert lane_capacity_for(64, 64) == 64
    assert lane_capacity_for(1000, 3) == 896       # 833.3 -> 896


# ---------------------------------------------------------------------------
# snapshot / restore, the guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("async_", ["false", "true"])
def test_snapshot_then_restore_mid_stream_equals_an_uninterrupted_run(
        async_):
    devs, vs = _events(2400, keys=20, seed=17)
    text = _app(_device(batch=128, async_=async_), head="@app:name('Snap')\n")
    rt0, m0, whole = _run(text, devs, vs, columns=True)
    m0.shutdown()
    cut = 1111                                  # mid-batch, mid-chunk
    m1 = SiddhiManager()
    store = InMemoryPersistenceStore()
    m1.set_persistence_store(store)
    rt1, _, first = _run(text, devs[:cut], vs[:cut], columns=True,
                         manager=m1)
    rt1.persist()
    m1.shutdown()
    m2 = SiddhiManager()
    m2.set_persistence_store(store)
    rest: list = []
    rt2 = m2.create_siddhi_app_runtime(text, playback=True)
    rt2.add_callback("Alerts", StreamCallback(
        lambda evs: rest.extend(tuple(e.data) for e in evs)))
    rt2.start()
    rt2.restore_last_revision()
    _send(rt2, devs[cut:], vs[cut:], True, start=cut)
    rt2.flush_device()
    try:
        assert _f32(first + rest) == _f32(whole)
        assert len(rest) > 0
    finally:
        m2.shutdown()


CHAOS = ("@app:chaos(seed='3', device.fail.p='{p}')\n"
         "@app:resilience(device.circuit.threshold='1000')\n")


@pytest.mark.parametrize("columns", [False, True], ids=["send", "columns"])
def test_a_failed_step_replays_through_the_host_partition(columns):
    """Every step fails (chaos): each batch's shadow replays, in order,
    through the per-key interpreter of the same block. No event is lost and
    the rows are the interpreter's."""
    devs, vs = _events(900, keys=12, seed=19)
    want = _interpreter(devs, vs)
    rt, m, rows = _run(_app(_device(), head=CHAOS.format(p="1.0")), devs, vs,
                       columns=columns)
    try:
        rep = rt.device_bridges[0].guard.report()
        assert rep["failures"] >= 7
        assert rep["fallback_events"] == 900 and rep["lost_events"] == 0
        assert rep["fallback_engine"] == "scalar"
        assert _f32(rows) == want
        assert not rt.partition_runtimes        # the fallback is the guard's
    finally:
        m.shutdown()


def test_some_failed_steps_lose_no_event():
    devs, vs = _events(1500, keys=12, seed=23)
    rt, m, rows = _run(_app(_device(), head=CHAOS.format(p="0.4")), devs, vs,
                       columns=True)
    try:
        bridge = rt.device_bridges[0]
        rep = bridge.guard.report()
        assert rep["failures"] > 0 and rep["lost_events"] == 0
        assert 0 < rep["fallback_events"] < 1500
        # every event went through exactly one of the two engines
        assert bridge.probe.events + rep["fallback_events"] == 1500
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# what does not lower
# ---------------------------------------------------------------------------

TWO_QUERIES = """
define stream S (dev string, v double);
partition with (dev of S) begin
@device(strict='{strict}', batch='64', slots='64', lanes='4')
from every e1=S[v > 50.0] -> e2=S[v > e1.v] select e1.v as v1, e2.v as v2
insert into Alerts;
from S[v > 99.0] select dev, v insert into Peaks;
end;
"""


def test_strict_raises_for_a_block_of_two_queries():
    m = SiddhiManager()
    try:
        with pytest.raises(DeviceCompileError, match="several queries"):
            m.create_siddhi_app_runtime(TWO_QUERIES.format(strict="true"),
                                        playback=True)
    finally:
        m.shutdown()


SEQUENCE = """
define stream S (dev string, v double);
partition with (dev of S) begin
@device(strict='{strict}', batch='64', slots='64', lanes='4')
from every e1=S[v > 50.0], e2=S[v > e1.v] within 4000
select e1.v as v1, e2.v as v2 insert into Alerts;
end;
"""


@pytest.mark.parametrize("text, why", [
    (TWO_QUERIES.format(strict="false"), "two queries"),
    (SEQUENCE.format(strict="false"), "a sequence"),
], ids=["two-queries", "sequence"])
def test_a_block_that_does_not_lower_keeps_the_host_tiers(text, why):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(text, playback=True)
        assert not rt.device_bridges, why
        assert len(rt.partition_runtimes) == 1
    finally:
        m.shutdown()


def test_strict_raises_for_a_sequence():
    """What a count state was until the served partition stepped the scan
    kernel too: a block that still does not lower (strictness is per key,
    a lane holds many keys)."""
    m = SiddhiManager()
    try:
        with pytest.raises(DeviceCompileError, match="per-key strictness"):
            m.create_siddhi_app_runtime(SEQUENCE.format(strict="true"),
                                        playback=True)
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# one program, one chunk a step, no loop over lanes or events
# ---------------------------------------------------------------------------

def test_one_compile_and_one_chunk_a_step_and_no_call_per_event(monkeypatch):
    devs, vs = _events(1000, keys=30, seed=29)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(_app(_device(batch=128, lanes=8)),
                                     playback=True)
    rows: list = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: rows.extend(tuple(e.data) for e in evs)))
    rt.start()
    r = rt.device_bridges[0].runtime
    chunks: list = []
    inner_collect = r.collect

    def collect(token):
        out = inner_collect(token)
        chunks.append(out)
        return out

    r.collect = collect
    hashed: list = []
    monkeypatch.setattr(tpu_partition, "_hash_key", lambda v: (
        hashed.append(v), zlib.crc32(str(v).encode()) & 0x7FFFFFFF)[1])
    lane_decodes: list = []
    inner_decode = type(r.compiler).decode_outputs
    monkeypatch.setattr(
        type(r.compiler), "decode_outputs",
        lambda self, ys, lane_batch=None: (
            lane_decodes.append(1) if lane_batch is None else None,
            inner_decode(self, ys, lane_batch))[1])
    try:
        # first pass: one crc32 a DISTINCT key, not an event
        _send(rt, devs[:500], vs[:500], True, chunk=100)
        assert sorted(hashed) == sorted(set(devs[:500].tolist()))
        assert len(hashed) == 30
        # known keys: a columnar chunk costs no hash and no encode call
        encodes: list = []
        dic = r.compiler.merged.dictionaries["s0_dev"]
        monkeypatch.setattr(dic, "encode", lambda s: encodes.append(s))
        _send(rt, devs[500:], vs[500:], True, chunk=100, start=500)
        rt.flush_device()               # a partial batch: the same program
        assert len(hashed) == 30 and not encodes
        assert r.vstep._cache_size() == 1           # full + partial batches
        assert len(chunks) == rt.device_bridges[0].probe.steps == 8
        assert all(isinstance(c, ColumnsOut) for c in chunks)
        assert not lane_decodes         # the stacked decode, no lane loop
        assert sum(len(c) for c in chunks) == len(rows) > 0
    finally:
        m.shutdown()


def test_the_per_event_send_hashes_a_key_once(monkeypatch):
    hashed: list = []
    monkeypatch.setattr(tpu_partition, "_hash_key", lambda v: (
        hashed.append(v), zlib.crc32(str(v).encode()) & 0x7FFFFFFF)[1])
    rt = PartitionedNFARuntime(_app(), num_partitions=4, key_attr="dev",
                               slot_capacity=32, lane_batch=64)
    for i in range(300):
        rt.send("S", [f"dev{i % 7}", float(i % 100)], 1000 + i)
    assert sorted(hashed) == sorted(f"dev{k}" for k in range(7))
    assert rt.lane_of("dev3") == (zlib.crc32(b"dev3") & 0x7FFFFFFF) % 4


def test_the_stacked_decode_equals_the_per_lane_decode():
    """On seeded stacked outputs (several matches in one lane sharing an
    event, empty lanes, every lane): the one-pass decode gives the rows the
    old loop over lanes gave, in its order."""
    import jax

    rt = PartitionedNFARuntime(_app(), num_partitions=6, key_attr="dev",
                               slot_capacity=16, lane_batch=32)
    nfa = rt.compiler
    width = 2 * 16 + 32
    rng = np.random.default_rng(31)
    for density in (0.0, 0.02, 0.3, 1.0):
        mask = rng.uniform(size=(6, width)) < density
        mask[4] = False                                # an empty lane
        ys = {"mask": mask,
              "j": rng.integers(0, 32, (6, width)).astype(np.int32),
              "ts": rng.integers(0, 10**6, (6, width))}
        for name, _, _ in nfa.out_specs:
            ys[name] = rng.uniform(0, 100, (6, width)).astype(np.float32)
        old = []
        for lane in range(6):
            lane_ys = jax.tree_util.tree_map(lambda x: x[lane], ys)
            old.extend(nfa.decode_outputs(lane_ys).rows())
        got = rt.decode_stacked(ys)
        assert isinstance(got, ColumnsOut)
        assert got.rows() == old
        assert len(got) == int(mask.sum())


# ---------------------------------------------------------------------------
# the lanes' rows, packed on the device (PR 34): what the host fetches
# ---------------------------------------------------------------------------

def _tap_decode(r, on_step):
    """Calls ``on_step(ys)`` with every step's un-decoded outputs."""
    inner = r._decode

    def decode(ys):
        on_step(ys)
        return inner(ys)

    r._decode = decode


@pytest.mark.parametrize("zipf", [False, True], ids=["uniform", "zipf"])
@pytest.mark.parametrize("served", [True, False], ids=["served", "direct"])
def test_the_packed_lane_tables_hold_the_full_tables_rows(served, zipf):
    """The partition fuzz's streams, step after step: the rows decoded from
    the lanes' packed ``[P, M]`` tables are the rows decoded from their
    whole candidate tables, element for element and in order, ``n`` counts
    them a lane, and the whole-table decode never ran."""
    devs, vs = _events(1500, keys=48, seed=7, zipf=zipf)
    want = _interpreter(devs, vs)
    steps: list = []
    m = None
    if served:
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(
            _app(_device(batch=128, slots=96, lanes=4)), playback=True)
        rows: list = []
        rt.add_callback("Alerts", StreamCallback(
            lambda evs: rows.extend(tuple(e.data) for e in evs)))
        rt.start()
        r = rt.device_bridges[0].runtime
    else:
        r = PartitionedNFARuntime(_app(), num_partitions=4, key_attr="dev",
                                  slot_capacity=96, lane_batch=64)
        rows = []
        r.callback = rows.extend
    nfa = r.compiler

    def on_step(ys):
        assert set(ys) == {"n", "mask", "j", "v1", "v2", "v3", "full"}
        packed = nfa.decode_outputs(ys, lane_batch=r.lane_batch)
        full = nfa.decode_outputs(ys["full"], lane_batch=r.lane_batch)
        assert_same_chunk(packed, full)
        n = np.asarray(ys["n"])
        assert n.shape == (4,) and int(n.sum()) == len(full)
        assert np.array_equal(np.asarray(ys["mask"]).sum(axis=1), n)
        steps.append(len(full))

    if served:
        _tap_decode(r, on_step)
    else:
        inner = r.decode_stacked
        r.decode_stacked = lambda ys: (on_step(ys), inner(ys))[1]
    try:
        if served:
            _send(rt, devs, vs, True, chunk=100)
            rt.flush_device()
        else:
            for i in range(len(vs)):
                r.send("S", [devs[i], float(vs[i])], 1000 + i)
            r.flush()
        assert nfa.M == r.lane_batch
        assert len(steps) > 5 and sum(steps) == len(rows) > 20
        assert "decode_full" not in (r.parts or {}) and r.drop_count == 0
        assert _f32(rows) == want
        if served:
            phases = rt.device_bridges[0].probe.phases
            assert phases.trackers["decode_full"].hist.count == 0
            assert phases.trackers["egress_decode"].hist.count == len(vs)
    finally:
        if m is not None:
            m.shutdown()


def _one_lane_closes_many(n_wait=80):
    """A stream in which ONE key closes ``n_wait`` partials with one event:
    they wait two states deep (two batches in the making), then all emit
    in one batch, in one lane."""
    devs, vs = _events(240, keys=12, seed=43)
    hot = np.array(["hot"] * (n_wait + 2), dtype=object)
    hot_v = np.array([52.0 - i / 1000 for i in range(n_wait)] + [60.0, 70.0])
    return (np.concatenate([devs[:120], hot, devs[120:]]),
            np.concatenate([vs[:120], hot_v, vs[120:]]))


@pytest.mark.parametrize("columns", [False, True], ids=["send", "columns"])
def test_a_lane_that_emits_more_than_its_packed_table_loses_no_row(columns):
    """``M`` (a lane's event capacity, 64 here) is no bound on a lane's rows:
    80 waiting partials close on one event. The decode reads the whole
    candidate tables for that batch and for no other: every row is
    delivered and equals the interpreter's, nothing is a drop, one
    ``decode_full`` is counted."""
    devs, vs = _one_lane_closes_many()
    want = _interpreter(devs, vs)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        _app(_device(batch=64, slots=128, lanes=4)), playback=True)
    rows: list = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: rows.extend(tuple(e.data) for e in evs)))
    rt.start()
    r = rt.device_bridges[0].runtime
    most: list = []
    _tap_decode(r, lambda ys: most.append(int(np.asarray(ys["n"]).max())))
    try:
        _send(rt, devs, vs, columns, chunk=50)
        rt.flush_device()
        assert r.compiler.M == 64
        assert sum(n > 64 for n in most) == 1 and max(most) >= 80
        assert _f32(rows) == want and len(rows) >= 80
        assert r.drop_count == 0 and r.lane_gauges["drops"] == 0
        phases = rt.device_bridges[0].probe.phases
        # event-weighted like every phase: that one batch's events
        assert 0 < phases.trackers["decode_full"].hist.count <= 64
        assert phases.trackers["egress_decode"].hist.count == len(vs)
        assert rt.device_bridges[0].probe.steps == len(most)
        rep = rt.observability.latency_report()["queries"]
        (entry,) = [v for v in rep.values() if "lanes" in v]
        assert "decode_full" in entry["phases"]
        assert entry["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-6)
    finally:
        m.shutdown()


def test_one_compile_and_only_the_packed_tables_cross_to_the_host(
        monkeypatch):
    """What one step of a served chain partition brings to the host: the
    fence fetches ``n`` (a count a lane), the decode ``(2 + outputs) x lanes
    x M`` elements in ONE ``device_get``, and nothing ``[.., P]`` wide (the
    whole candidate tables stay on the device); one compile of ``vstep``."""
    import jax

    devs, vs = _events(1000, keys=30, seed=29)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        _app(_device(batch=128, lanes=8, slots=64)), playback=True)
    rows: list = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: rows.extend(tuple(e.data) for e in evs)))
    rt.start()
    r = rt.device_bridges[0].runtime
    lanes, slots, rows_m = 8, 64, lane_capacity_for(128, 8)
    wide = 2 * slots + rows_m                   # P of the 3-state chain
    fenced, fetched, handed = [], [], []
    inner_fence, inner_decode = r._fence, r._decode
    real_get = jax.device_get
    in_decode = [False]

    def device_get(x):
        if in_decode[0]:
            fetched[-1].append([tuple(a.shape) for a in jax.tree.leaves(x)])
        return real_get(x)

    def decode(ys):
        handed.append(jax.tree.map(lambda a: tuple(a.shape), ys))
        fetched.append([])
        in_decode[0] = True
        try:
            return inner_decode(ys)
        finally:
            in_decode[0] = False

    monkeypatch.setattr(jax, "device_get", device_get)
    r._fence = lambda first: (fenced.append(tuple(first.shape)),
                              inner_fence(first))[1]
    r._decode = decode
    try:
        _send(rt, devs, vs, True, chunk=100)
        rt.flush_device()
        assert r.compiler.M == rows_m and r.fence_key == "n"
        assert r.vstep._cache_size() == 1
        assert len(handed) == rt.device_bridges[0].probe.steps == 8
        assert rows
        table = {k: (lanes, rows_m) for k in ("mask", "j", "v1", "v2", "v3")}
        for ys in handed:       # on the device: both tables and the count
            assert ys == {"n": (lanes,), **table,
                          "full": {k: (lanes, wide) for k in table}}
        assert fenced == [(lanes,)] * 8
        for step in fetched:    # to the host: n again (cached), ONE table
            assert step == [[(lanes,)], [(lanes, rows_m)] * 5]
            assert sum(int(np.prod(s)) for s in step[1]) \
                == (2 + 3) * lanes * rows_m
        assert rt.device_bridges[0].probe.phases.trackers[
            "decode_full"].count == 0
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# the flat builder, by itself
# ---------------------------------------------------------------------------

def _builder(capacity=16, lanes=4, lane_capacity=4):
    rt = PartitionedNFARuntime(_app(), num_partitions=lanes, key_attr="dev",
                               slot_capacity=8, batch=capacity,
                               lane_batch=lane_capacity)
    assert isinstance(rt.builder, LaneBatchBuilder)
    return rt, rt.builder


def test_the_flat_builder_takes_a_chunk_as_far_as_a_full_lane():
    rt, b = _builder()
    lane = {d: rt.lane_of(d) for d in ("a", "b", "c", "d", "e", "f")}
    same = [d for d in lane if lane[d] == lane["a"]]
    other = next(d for d in lane if lane[d] != lane["a"])
    # four of lane(a), then one of another lane, then a fifth of lane(a)
    devs = np.array([same[0]] * 4 + [other] + [same[-1]] + [other],
                    dtype=object)
    ts = np.arange(7, dtype=np.int64)
    cols = {"dev": devs, "v": np.arange(7, dtype=np.float64)}
    assert b.append_columns(cols, ts) == 5          # stops at the fifth
    assert b.full and b.lane_full and len(b) == 5
    batch = b.emit()
    assert batch["count"] == 5 and batch["valid"].sum() == 5
    assert batch["valid"][:5].all() and not batch["valid"][5:].any()
    assert batch["lane"][:5].tolist() == [lane["a"]] * 4 + [lane[other]]
    assert not b.lane_full and len(b) == 0
    assert b.append_columns(cols, ts, 5) == 2       # the rest, in order
    assert b.emit()["cols"]["s0_v"][:2].tolist() == [5.0, 6.0]


def test_the_flat_builders_snapshot_keeps_the_lanes():
    rt, b = _builder()
    for i, d in enumerate(["a", "b", "a", "c"]):
        b.append("S", [d, float(i)], 100 + i)
    snap = b.snapshot()
    rt2, b2 = _builder()
    for d in ("a", "b", "c"):
        rt2.lane_of(d)
    b2.restore(snap)
    assert len(b2) == 4
    assert b2.emit()["lane"][:4].tolist() == snap["lane"].tolist()


def test_dispatch_lays_the_flat_batch_out_by_lane_in_arrival_order():
    rt, b = _builder(capacity=12, lanes=3, lane_capacity=8)
    names = [f"k{i}" for i in range(12)]
    for i, d in enumerate(names):
        b.append("S", [d, float(i)], 500 + i)
    batch = b.emit()
    cols, tag, ts, ts_base, counts = rt._lay_out(batch)
    assert counts.sum() == 12 and ts_base.tolist() == [500] * 3
    for lane in range(3):
        mine = [i for i, d in enumerate(names) if rt.lane_of(d) == lane]
        assert counts[lane] == len(mine)
        assert cols["s0_v"][lane, :len(mine)].tolist() == [float(i)
                                                           for i in mine]
        assert ts[lane, :len(mine)].tolist() == mine     # deltas from 500
    # the harness's fault: half the flat batch left out
    half = dict(batch, count=6)
    assert rt._lay_out(half)[4].sum() == 6


def test_the_route_tracker_and_the_gauges_are_reported():
    devs, vs = _events(600, keys=16, seed=37)
    rt, m, _ = _run(_app(_device()), devs, vs, columns=True)
    try:
        rep = rt.observability.latency_report()["queries"]
        (q, entry), = [(k, v) for k, v in rep.items() if "lanes" in v]
        assert "route" in entry["phases"]
        assert entry["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-6)
        assert set(entry["lanes"]) == {"fullest_table_share",
                                       "fullest_lane_events", "drops"}
        assert entry["kernel"] == "blocked"
        gauges = rt.ctx.statistics_manager.report()
        gauges = gauges.get("gauges", gauges)
        assert any(k.endswith("lanes_fullest_table_share") for k in gauges)
        assert any(k.endswith("lanes_kernel_scan") for k in gauges)
    finally:
        m.shutdown()


def test_table_overflow_is_warned_of_not_silent(caplog):
    devs, vs = _events(1500, keys=8, seed=41)
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu.device"):
        rt, m, _ = _run(_app(_device(slots=8)), devs, vs, columns=True)
    try:
        assert rt.device_bridges[0].runtime.lane_gauges["drops"] > 0
        assert any("dropped from full lane tables" in r.getMessage()
                   for r in caplog.records)
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# a count state in the block: the lanes step the per-event scan kernel
# (ISSUE 33), held to the same interpreter
# ---------------------------------------------------------------------------

KLEENE = ("from every e1=S[v > 50.0] -> e2=S[v > e1.v]{count} -> "
          "e3=S[v < e1.v] within {within}\n"
          "select e1.v as v1, {select}, e3.v as back insert into Alerts;")
KLEENE_SELECT = "e2[0].v as first, e2[last].v as peak"


def _kleene(device: str = "", count: str = "<3:>", within: int = 4000,
            select: str = KLEENE_SELECT, head: str = "") -> str:
    return (f"{head}define stream S (dev string, v double);\n"
            f"partition with (dev of S) begin\n{device}\n"
            + KLEENE.format(count=count, within=within, select=select)
            + "\nend;\n")


def _null_f32(rows) -> list:
    """``_f32`` for rows that may hold NULL (``e2[k]`` never reached)."""
    return sorted(tuple(-1.0 if x is None else float(np.float32(x))
                        for x in r) for r in rows)


def _kleene_interpreter(devs, vs, **kw) -> list:
    rt, m, rows = _run(_kleene(**kw), devs, vs)
    assert not rt.device_bridges and len(rt.partition_runtimes) == 1
    m.shutdown()
    return _null_f32(rows)


@pytest.mark.parametrize("columns", [False, True], ids=["send", "columns"])
@pytest.mark.parametrize("zipf", [False, True], ids=["uniform", "zipf"])
def test_served_count_state_rows_equal_the_interpreters(columns, zipf):
    devs, vs = _events(3000, keys=40, zipf=zipf)
    want = _kleene_interpreter(devs, vs)
    assert len(want) > 100
    rt, m, rows = _run(_kleene(_device()), devs, vs, columns=columns)
    try:
        assert _null_f32(rows) == want
        assert len(rt.device_bridges) == 1
        assert not (rt.partition_runtimes or rt.host_bridges
                    or rt.fleet_bridges or rt.query_runtimes)
        bridge = rt.device_bridges[0]
        assert bridge.kind == "partition" and bridge.guard is not None
        r = bridge.runtime
        assert r.kernel == "scan" and not r.compiler.blocked
        assert [s.kind for s in r.compiler.states] == ["stream", "count",
                                                       "stream"]
        assert bridge.probe.events == 3000
        assert bridge.guard.report()["failures"] == 0
        assert r.lane_gauges["drops"] == 0
        assert 0.0 < r.lane_gauges["fullest_table_share"] < 1.0
        rep = rt.observability.latency_report()["queries"]
        assert [v["kernel"] for v in rep.values() if "lanes" in v] == ["scan"]
    finally:
        m.shutdown()


@pytest.mark.parametrize("count, select", [
    ("<3:>", KLEENE_SELECT),
    ("<2:5>", KLEENE_SELECT),
    ("<3:>", "e2[0].v as first, e2[1].v as second, e2[last].v as peak"),
    ("<2:5>", "e2[1].v as second, e2[3].v as fourth"),
], ids=["3-unbounded", "2-to-5", "occurrence-1", "occurrence-3-may-be-null"])
def test_count_bounds_and_occurrence_indexes(count, select):
    devs, vs = _events(2500, keys=20, seed=7)
    want = _kleene_interpreter(devs, vs, count=count, select=select)
    assert len(want) > 50
    rt, m, rows = _run(_kleene(_device(), count=count, select=select), devs,
                       vs, columns=True)
    try:
        assert _null_f32(rows) == want
        if "e2[3]" in select:       # a closure of two or three: NULL
            assert any(r[2] is None for r in rows)
            assert any(r[2] is not None for r in rows)
    finally:
        m.shutdown()


def test_count_state_more_keys_than_lanes_and_a_closure_over_three_batches():
    """64 keys over 4 lanes in batches of 96: a key sees about 1.5 events a
    batch, so every closure of three or more spans three batches at least."""
    devs, vs = _events(2400, keys=64, seed=5)
    want = _kleene_interpreter(devs, vs)
    assert len(want) > 50
    rt, m, rows = _run(_kleene(_device(batch=96, lanes=4, slots=512)), devs,
                       vs, columns=True, chunk=50)
    try:
        assert _null_f32(rows) == want
        assert rt.device_bridges[0].probe.steps >= 25
        assert rt.device_bridges[0].runtime.lane_gauges["drops"] == 0
    finally:
        m.shutdown()


def test_count_state_within_expires_across_batches():
    devs, vs = _events(2500, keys=10, seed=13)
    want = _kleene_interpreter(devs, vs, within=60)
    loose = _kleene_interpreter(devs, vs, within=4000)
    assert 0 < len(want) < len(loose)
    rt, m, rows = _run(_kleene(_device(batch=64, lanes=4), within=60), devs,
                       vs, columns=True, chunk=64)
    try:
        assert _null_f32(rows) == want
    finally:
        m.shutdown()


@pytest.mark.parametrize("columns", [False, True], ids=["send", "columns"])
def test_count_state_a_full_lane_seals_the_batch(columns):
    n = 1200
    rng = np.random.default_rng(21)
    hot = rng.uniform(size=n) < 0.7
    devs = np.where(hot, "hot", np.array(
        [f"dev{k}" for k in rng.integers(0, 30, n)], dtype=object))
    devs = devs.astype(object)
    vs = np.round(rng.uniform(0.0, 100.0, n), 3)
    want = _kleene_interpreter(devs, vs)
    rt, m, rows = _run(_kleene(_device(batch=256, lanes=8, slots=512)), devs,
                       vs, columns=columns, chunk=200)
    try:
        bridge = rt.device_bridges[0]
        assert bridge.probe.flush_causes.get("lane_full", 0) >= 4
        assert bridge.probe.events == n
        assert _null_f32(rows) == want
    finally:
        m.shutdown()


@pytest.mark.parametrize("async_", ["false", "true"])
def test_count_state_snapshot_then_restore_mid_closure(async_):
    devs, vs = _events(2400, keys=20, seed=17)
    text = _kleene(_device(batch=128, async_=async_),
                   head="@app:name('SnapK')\n")
    rt0, m0, whole = _run(text, devs, vs, columns=True)
    m0.shutdown()
    cut = 1111
    m1 = SiddhiManager()
    store = InMemoryPersistenceStore()
    m1.set_persistence_store(store)
    rt1, _, first = _run(text, devs[:cut], vs[:cut], columns=True,
                         manager=m1)
    # closures are open at the cut: partials collecting, some already at 3
    pend = rt1.device_bridges[0].runtime.state["pending"]["p1"]
    open_counts = np.asarray(pend["count"])[np.asarray(pend["valid"])]
    assert (open_counts >= 3).any() and (open_counts < 3).any()
    rt1.persist()
    m1.shutdown()
    m2 = SiddhiManager()
    m2.set_persistence_store(store)
    rest: list = []
    rt2 = m2.create_siddhi_app_runtime(text, playback=True)
    rt2.add_callback("Alerts", StreamCallback(
        lambda evs: rest.extend(tuple(e.data) for e in evs)))
    rt2.start()
    rt2.restore_last_revision()
    _send(rt2, devs[cut:], vs[cut:], True, start=cut)
    rt2.flush_device()
    try:
        assert _null_f32(first + rest) == _null_f32(whole)
        assert len(rest) > 0
    finally:
        m2.shutdown()


def test_count_state_a_failed_step_replays_through_the_host_partition():
    devs, vs = _events(900, keys=12, seed=19)
    want = _kleene_interpreter(devs, vs)
    rt, m, rows = _run(_kleene(_device(), head=CHAOS.format(p="1.0")), devs,
                       vs, columns=True)
    try:
        rep = rt.device_bridges[0].guard.report()
        assert rep["failures"] >= 7
        assert rep["fallback_events"] == 900 and rep["lost_events"] == 0
        assert rep["fallback_engine"] == "scalar"
        assert _null_f32(rows) == want
        assert not rt.partition_runtimes
    finally:
        m.shutdown()


def test_count_state_one_compile_one_chunk_and_one_row_table_a_step(
        monkeypatch):
    """What one step of a served count-state partition brings to the host,
    as the chain's does: the fence fetches ``n`` (a count a lane), the
    decode ``(2 + outputs + null masks) x P x M`` elements in ONE
    ``device_get`` with ``M`` the lane's event capacity; ``full``, ``C + B``
    rows a lane for this query, stays on the device, and nothing of the
    ``[B, P, 1, C]`` emit grids leaves the step."""
    import jax

    devs, vs = _events(1000, keys=30, seed=29)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        _kleene(_device(batch=128, lanes=8, slots=64)), playback=True)
    rows: list = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: rows.extend(tuple(e.data) for e in evs)))
    rt.start()
    r = rt.device_bridges[0].runtime
    chunks, fenced, fetched, handed = [], [], [], []
    inner_collect, inner_decode, inner_fence = r.collect, r._decode, r._fence
    real_get = jax.device_get
    in_decode = [False]

    def collect(token):
        out = inner_collect(token)
        chunks.append(out)
        return out

    def device_get(x):
        if in_decode[0]:
            fetched[-1].append([tuple(a.shape) for a in jax.tree.leaves(x)])
        return real_get(x)

    def decode(ys):
        handed.append(jax.tree.map(lambda a: tuple(a.shape), ys))
        fetched.append([])
        in_decode[0] = True
        try:
            return inner_decode(ys)
        finally:
            in_decode[0] = False

    monkeypatch.setattr(jax, "device_get", device_get)
    r._fence = lambda first: (fenced.append(tuple(first.shape)),
                              inner_fence(first))[1]
    r.collect, r._decode = collect, decode
    try:
        _send(rt, devs, vs, True, chunk=100)
        rt.flush_device()
        lanes, slots, rows_m = 8, 64, lane_capacity_for(128, 8)
        assert r.compiler.M == rows_m and r.fence_key == "n"
        assert r.compiler._row_capacity() == slots + rows_m
        assert r.vstep._cache_size() == 1
        assert len(chunks) == rt.device_bridges[0].probe.steps == 8
        assert all(isinstance(c, ColumnsOut) for c in chunks)
        assert sum(len(c) for c in chunks) == len(rows) > 0
        # mask, j and the four outputs (none can be NULL here)
        table = {k: (lanes, rows_m)
                 for k in ("mask", "j", "v1", "first", "peak", "back")}
        for ys in handed:       # on the device: both tables and the count
            assert ys == {"n": (lanes,), **table, "full": {
                k: (lanes, slots + rows_m) for k in table}}
        assert fenced == [(lanes,)] * 8
        for step in fetched:    # to the host: n again (cached), ONE table
            assert step == [[(lanes,)], [(lanes, rows_m)] * 6]
            assert sum(int(np.prod(shape)) for shape in step[1]) \
                == (2 + 4 + 0) * lanes * rows_m
        assert rt.device_bridges[0].probe.phases.trackers[
            "decode_full"].count == 0
    finally:
        m.shutdown()


def _one_lane_closes_many_closures(n_wait=80):
    """A stream in which ONE key closes ``n_wait`` Kleene closures with one
    event: rising readings open a closure each and collect every later one,
    the reading back under the first closes all that hold three."""
    devs, vs = _events(240, keys=12, seed=43)
    hot = np.array(["hot"] * (n_wait + 4), dtype=object)
    hot_v = np.array([52.0 + i / 1000 for i in range(n_wait + 3)] + [10.0])
    return (np.concatenate([devs[:120], hot, devs[120:]]),
            np.concatenate([vs[:120], hot_v, vs[120:]]))


@pytest.mark.parametrize("columns", [False, True], ids=["send", "columns"])
def test_count_state_a_lane_that_emits_more_than_its_packed_table_loses_no_row(
        columns):
    """The scan kernel's ``M`` (a lane's event capacity, 64 here) is no bound
    on a lane's rows either: 80 closures of one key close on one event. In
    that batch exactly one lane passes ``M``, so the step packs at the size
    of ``full`` for all lanes and the decode reads ``full``, for that batch
    and for no other: every lane's rows equal the interpreter's, nothing is
    a drop, one ``decode_full`` is counted."""
    devs, vs = _one_lane_closes_many_closures()
    want = _kleene_interpreter(devs, vs)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        _kleene(_device(batch=64, slots=128, lanes=4)), playback=True)
    rows: list = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: rows.extend(tuple(e.data) for e in evs)))
    rt.start()
    r = rt.device_bridges[0].runtime
    counts: list = []

    def on_step(ys):
        n = np.asarray(ys["n"])
        counts.append(n)
        # ``full`` holds every lane's rows, whichever pack ran
        assert np.array_equal(np.asarray(ys["full"]["mask"]).sum(axis=1), n)
        assert np.array_equal(np.asarray(ys["mask"]).sum(axis=1),
                              np.minimum(n, 64))

    _tap_decode(r, on_step)
    try:
        _send(rt, devs, vs, columns, chunk=50)
        rt.flush_device()
        assert r.kernel == "scan" and r.compiler.M == 64
        over = [n for n in counts if n.max() > 64]
        assert len(over) == 1 and over[0].max() >= 80
        assert int((over[0] > 64).sum()) == 1           # exactly one lane
        assert _null_f32(rows) == want and len(rows) >= 80
        assert r.drop_count == 0 and r.lane_gauges["drops"] == 0
        phases = rt.device_bridges[0].probe.phases
        # event-weighted like every phase: that one batch's events
        assert 0 < phases.trackers["decode_full"].hist.count <= 64
        assert phases.trackers["egress_decode"].hist.count == len(vs)
        assert rt.device_bridges[0].probe.steps == len(counts)
        rep = rt.observability.latency_report()["queries"]
        (entry,) = [v for v in rep.values() if "lanes" in v]
        assert "decode_full" in entry["phases"]
        assert entry["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-6)
    finally:
        m.shutdown()
