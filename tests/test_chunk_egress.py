"""Device egress goes out a batch at a time (ISSUE 26).

``collect`` returns one ``ColumnsOut`` chunk; the bridge hands the output
junction that chunk whole — as columns when every subscriber takes columns,
else as one chunk of events through one ``send_events`` — and a
``StreamCallback`` gets one list per delivered chunk. Pinned here, on the CPU
backend: the rows, their order, values and timestamps are the scalar
interpreter's for a stream, an NFA and a join query (string columns, NULL
cells, empty batches, a hopping window's drain, a guard replay included);
what each kind of subscriber sees; the counters that say the chunk path is
the one engaged; the serial waterfall still reconciling.
"""

import numpy as np
import pytest

from siddhi_tpu import QueryCallback, SiddhiManager, StreamCallback
from siddhi_tpu.core.event import Event, StreamEvent
from util_parity import rows_equal


@pytest.fixture
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


# values exact in float32: the device computes DOUBLE in float32
def _s_events(n, seed=5):
    rng = np.random.default_rng(seed)
    return [("S", [f"k{int(rng.integers(4))}", float(rng.integers(0, 400)) / 4,
                   int(rng.integers(1, 1000))], 1000 + i) for i in range(n)]


STREAM_APP = """
define stream S (sym string, price double, vol long);
{device}
from S[price > 50.0]#window.length(4)
select sym, sum(vol) as total, count() as c, price insert into O;
"""

# every e1 has at most one pending e2 per event here: the order of matches is
# the order of their last events, on the device as in the interpreter
NFA_APP = """
define stream S (sym string, price double, vol long);
{device}
from every e1=S[price > 90.0] -> e2=S[price < 10.0] within 40
select e1.sym as s1, e2.sym as s2, e1.price as p1, e2.vol as v2 insert into O;
"""

JOIN_APP = """
define stream L (k string, v long);
define stream R (k string, w double);
{device}
from L#window.length(1) left outer join R#window.length(1) on L.k == R.k
select L.k as k, L.v as v, R.k as rk, R.w as w insert into O;
"""


def _join_events(n, seed=9):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        k = f"k{int(rng.integers(3))}"
        if rng.random() < 0.5:
            out.append(("L", [k, int(rng.integers(100))], 1000 + i))
        else:
            out.append(("R", [k, float(rng.integers(0, 200)) / 2], 1000 + i))
    return out


CASES = {
    "stream": (STREAM_APP, _s_events(200), "@device(batch='16', strict='true')"),
    "stream-async": (STREAM_APP, _s_events(200),
                     "@device(batch='16', strict='true', async='true')"),
    "nfa": (NFA_APP, _s_events(400, seed=6),
            "@device(batch='32', slots='16', strict='true')"),
    "nfa-async": (NFA_APP, _s_events(400, seed=6),
                  "@device(batch='32', slots='16', strict='true', "
                  "async='true')"),
    "join": (JOIN_APP, _join_events(120), "@device(batch='8', strict='true')"),
}


def _run(manager, app, events, out="O", subscribe=None):
    """Feed ``events`` ((stream, row, ts)) and return the StreamCallback's
    calls: one list of (timestamp, data) per call."""
    rt = manager.create_siddhi_app_runtime(app, playback=True)
    calls = []
    rt.add_callback(out, StreamCallback(
        lambda evs: calls.append([(e.timestamp, e.data) for e in evs])))
    if subscribe is not None:
        subscribe(rt)
    rt.start()
    for sid, row, ts in events:
        rt.input_handler(sid).send(list(row), timestamp=ts)
    rt.flush_device()
    return rt, calls


def _flat(calls):
    return [row for call in calls for _ts, row in call]


def _assert_same_rows(expected, got):
    assert len(expected) == len(got)
    for e, g in zip(expected, got):
        assert rows_equal(e, g), (e, g)


def _with_query_callback(rt):
    """A query callback in front: the chunk has to go out as events."""
    rt.add_query_callback("q", QueryCallback(lambda ts, cur, exp: None))


@pytest.mark.parametrize("shape", ["columns", "events"])
@pytest.mark.parametrize("case", list(CASES))
def test_chunk_egress_gives_the_interpreters_rows_in_order(manager, case,
                                                           shape):
    """Both shapes of a chunk: columns (a StreamCallback alone builds its
    events from them) and one chunk of events (a query callback listens
    too)."""
    app, events, device = CASES[case]
    _rt, host = _run(manager, app.format(device=""), events)
    rt, dev = _run(manager, app.format(device="@info(name='q') " + device),
                   events,
                   subscribe=_with_query_callback if shape == "events"
                   else None)
    expected, got = _flat(host), _flat(dev)
    assert expected, "the corpus must produce rows"
    _assert_same_rows(expected, got)
    # strings come out as strings and NULL cells as None, never as codes
    if case == "join":
        assert any(r[2] is None and r[3] is None for r in got)
        assert any(isinstance(r[2], str) for r in got)
    assert all(isinstance(r[0], str) for r in got)
    # the values are Python scalars, as the row loop made them
    assert {type(v) for r in got for v in r} <= {str, int, float, type(None)}
    # one callback call per batch that had rows, not one per row
    bridge = rt.device_bridges[0]
    other = "events" if shape == "columns" else "columns"
    assert len(dev) == bridge.egress[shape][0] < len(got)
    assert bridge.egress[shape][1] == len(got)
    assert bridge.egress[other] == [0, 0]


def test_a_stream_callback_gets_one_list_per_batch_stamped_with_its_last_ts(
        manager):
    """One ``receive`` per delivered batch; every row of it carries the
    batch's last event time; the concatenation is the per-row sequence."""
    app, events, device = CASES["stream"]
    rt, calls = _run(manager, app.format(device=device), events)
    bridge = rt.device_bridges[0]
    assert bridge.probe.steps == len(events) // 16 + 1
    # a batch of 16 ends at event 16k - 1 (ts 1000 + 16k - 1)
    for call in calls:
        stamps = {ts for ts, _row in call}
        assert len(stamps) == 1
        ts = stamps.pop()
        assert (ts - 1000 + 1) % 16 == 0 or ts == 1000 + len(events) - 1
    assert [ts for call in calls for ts, _ in call] == \
        sorted(ts for call in calls for ts, _ in call)


def test_an_empty_batch_delivers_nothing(manager):
    app, _events, device = CASES["stream"]
    quiet = [("S", ["k0", 1.0, 1], 1000 + i) for i in range(40)]  # all filtered
    rt, calls = _run(manager, app.format(device=device), quiet)
    bridge = rt.device_bridges[0]
    assert bridge.probe.steps == 3 and calls == []
    assert bridge.egress == {"columns": [0, 0], "events": [0, 0]}
    assert rt.ctx.stream_junctions["O"].throughput == 0


def test_an_empty_chunk_is_falsy_and_has_no_rows():
    from siddhi_tpu.tpu.nfa import DeviceNFARuntime
    rt = DeviceNFARuntime(NFA_APP.format(device=""), slot_capacity=8,
                          batch_capacity=8)
    rt.send("S", ["k0", 1.0, 1], 1000)
    out = rt.flush()
    assert not out and len(out) == 0 and out.rows() == []


HOP_APP = """
define stream S (sym string, price double, vol long);
{device}
from S#window.hopping(1 sec, 400)
select sum(price) as total, count() as c, max(price) as hi insert into O;
"""


@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_a_hopping_windows_drain_follows_its_batch_in_order(manager,
                                                           async_mode):
    """Long gaps span more hops than a step flushes: the drain steps' chunks
    are appended behind the batch's own, in order (one delivery)."""
    rng = np.random.default_rng(26)
    ts, events = 1000, []
    for _ in range(30):
        ts += int(rng.choice([50, 300, 4000]))
        events.append(("S", ["a", float(rng.integers(0, 400)) / 4, 1], ts))
    device = "@device(batch='4', strict='true'%s)" % (
        ", async='true'" if async_mode else "")
    _rt, host = _run(manager, HOP_APP.format(device=""), events)
    rt, dev = _run(manager, HOP_APP.format(device=device), events)
    assert len(_flat(dev)) > len(events)      # the drains did emit
    _assert_same_rows(_flat(host)[:len(_flat(dev))], _flat(dev))
    assert rt.device_bridges[0].egress["columns"][0] == len(dev)


@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_a_guard_replay_delivers_the_batch_in_its_place(manager, async_mode):
    app, events, _device = CASES["stream"]
    device = "@device(batch='16', strict='true'%s)" % (
        ", async='true'" if async_mode else "")
    _rt, host = _run(manager, app.format(device=""), events)

    def sabotage(rt):
        compiled = rt.device_bridges[0].runtime.compiled
        inner, calls = compiled.decode_outputs, [0]

        def decode(out):
            calls[0] += 1
            if calls[0] == 3:           # the third batch's collect fails
                raise RuntimeError("sabotaged decode")
            return inner(out)

        compiled.decode_outputs = decode

    rt, dev = _run(manager, app.format(device=device), events,
                   subscribe=sabotage)
    guard = rt.device_bridges[0].guard
    assert guard.failures == 1 and guard.fallback_events == 16
    # the window state on the device did see the failed batch; the host
    # replay starts its window empty, so compare what no window carries:
    # the rows' keys and prices, which are per event
    assert [(r[0], r[3]) for r in _flat(dev)] == \
        [(r[0], r[3]) for r in _flat(host)]


@pytest.mark.parametrize("shape", ["columns", "events"])
def test_one_altered_cell_of_a_chunk_is_one_wrong_row_and_none_missing(
        manager, shape):
    """A fault planted where the answer is produced (the benchmark's
    ``answer altered``): one cell of one chunk changed between ``collect``
    and ``deliver`` reaches the callback as that one row wrong, in its place,
    every other row as it was."""
    app, events, device = CASES["stream"]
    _rt, host = _run(manager, app.format(device=""), events)

    def alter(rt):
        runtime = rt.device_bridges[0].runtime
        inner, chunks = runtime.collect, [0]

        def collect(token):
            out = inner(token)
            if out:
                chunks[0] += 1
                if chunks[0] == 2:
                    out.decoded()["total"][0] += 1
            return out

        runtime.collect = collect
        if shape == "events":
            _with_query_callback(rt)

    rt, dev = _run(manager, app.format(device="@info(name='q') " + device),
                   events, subscribe=alter)
    expected, got = _flat(host), _flat(dev)
    assert len(got) == len(expected)
    wrong = [i for i, (e, g) in enumerate(zip(expected, got))
             if not rows_equal(e, g)]
    assert wrong == [len(dev[0])]           # the second chunk's first row
    assert got[wrong[0]][1] == expected[wrong[0]][1] + 1
    assert rt.device_bridges[0].guard.failures == 0


def _count_constructions(monkeypatch):
    counts = {"StreamEvent": 0, "Event": 0}
    se_init, ev_init = StreamEvent.__init__, Event.__init__

    def se(self, *a, **k):
        counts["StreamEvent"] += 1
        se_init(self, *a, **k)

    def ev(self, *a, **k):
        counts["Event"] += 1
        ev_init(self, *a, **k)

    own = Event._own

    def ev_own(timestamp, data):        # built from a chunk's columns
        counts["Event"] += 1
        return own(timestamp, data)

    monkeypatch.setattr(StreamEvent, "__init__", se)
    monkeypatch.setattr(Event, "__init__", ev)
    monkeypatch.setattr(Event, "_own", staticmethod(ev_own))
    return counts


def _send_columns(rt, events, chunk=10):
    ih = rt.input_handler("S")
    for s in range(0, len(events), chunk):
        part = events[s:s + chunk]
        ih.send_columns(
            {"sym": np.asarray([r[0] for _s, r, _t in part], dtype=object),
             "price": np.asarray([r[1] for _s, r, _t in part]),
             "vol": np.asarray([r[2] for _s, r, _t in part], dtype=np.int64)},
            np.asarray([t for _s, _r, t in part], dtype=np.int64))


@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_a_rows_callback_gets_columns_and_no_event_is_built(
        manager, monkeypatch, async_mode):
    app, events, _device = CASES["stream"]
    device = "@device(batch='16', strict='true'%s)" % (
        ", async='true'" if async_mode else "")
    _rt, host = _run(manager, app.format(device=""), events)
    rt = manager.create_siddhi_app_runtime(app.format(device=device),
                                           playback=True)
    chunks = []
    rt.add_rows_callback("O", lambda cols, ts, n: chunks.append(
        ({k: np.asarray(v).copy() for k, v in cols.items()},
         np.asarray(ts).copy(), n)))
    rt.start()
    counts = _count_constructions(monkeypatch)
    _send_columns(rt, events)
    rt.flush_device()
    assert counts == {"StreamEvent": 0, "Event": 0}
    bridge = rt.device_bridges[0]
    assert bridge.egress["events"] == [0, 0]
    assert bridge.egress["columns"] == [len(chunks),
                                        sum(n for _c, _t, n in chunks)]
    got = []
    for cols, ts, n in chunks:
        assert list(cols) == ["sym", "total", "c", "price"]
        assert ts.dtype == np.int64 and ts.shape == (n,)
        assert len(set(ts.tolist())) == 1       # the batch's last event time
        assert cols["sym"].dtype == object      # decoded, not codes
        got.extend(list(r) for r in zip(*(cols[k].tolist() for k in cols)))
    _assert_same_rows(_flat(host), got)


CHAIN_APP = """
define stream S (sym string, price double, vol long);
{d1}
from S[price > 50.0] select sym, price, vol insert into Mid;
{d2}
from Mid#window.length(3) select sym, sum(vol) as total insert into O;
"""


def test_a_device_query_feeds_a_device_query_columns(manager, monkeypatch):
    """The downstream bridge's columnar receiver takes the chunk: no
    per-row object between the two steps."""
    events = _s_events(160, seed=12)
    _rt, host = _run(manager, CHAIN_APP.format(d1="", d2=""), events)
    rt = manager.create_siddhi_app_runtime(CHAIN_APP.format(
        d1="@device(batch='16', strict='true')",
        d2="@device(batch='8', strict='true')"), playback=True)
    got = []
    rt.add_rows_callback("O", lambda cols, ts, n: got.extend(
        list(r) for r in zip(*(np.asarray(cols[k]).tolist() for k in cols))))
    rt.start()
    counts = _count_constructions(monkeypatch)
    _send_columns(rt, events, chunk=16)
    rt.flush_device()
    assert counts == {"StreamEvent": 0, "Event": 0}
    first, second = rt.device_bridges
    assert first.egress["columns"][0] >= 1 and first.egress["events"] == [0, 0]
    assert second.probe.events == first.egress["columns"][1]
    _assert_same_rows(_flat(host), got)


def test_a_stream_callback_builds_its_events_from_the_columns(manager,
                                                             monkeypatch):
    """Alone or beside a RowsCallback it takes the columnar chunk: one
    ``Event`` a row, owning its row, and no ``StreamEvent`` at all."""
    app, events, device = CASES["stream"]
    rt = manager.create_siddhi_app_runtime(app.format(device=device),
                                           playback=True)
    calls, seen = [], []
    rt.add_callback("O", StreamCallback(calls.append))
    rt.add_rows_callback("O", lambda cols, ts, n: seen.append(n))
    rt.start()
    counts = _count_constructions(monkeypatch)
    _send_columns(rt, events)
    rt.flush_device()
    rows = sum(len(c) for c in calls)
    assert counts == {"StreamEvent": 0, "Event": rows} and rows > len(calls)
    assert seen == [len(c) for c in calls]
    assert rt.device_bridges[0].egress == {"columns": [len(calls), rows],
                                           "events": [0, 0]}
    assert rt.ctx.stream_junctions["O"].throughput == rows


def test_a_subscriber_that_takes_events_only_degrades_the_chunk_once(manager):
    """Mixed subscribers: the junction is not columns-capable, so ONE chunk
    of events goes out; the StreamCallback gets it as one list and the
    RowsCallback, which has no chunk form, event by event."""
    app, events, device = CASES["stream"]
    seen, plain = [], []

    class Plain:
        def receive(self, event):
            plain.append(event.data)

    def subscribe(rt):
        rt.add_rows_callback("O", lambda cols, ts, n: seen.append(n))
        rt.ctx.stream_junctions["O"].subscribe(Plain())

    rt, calls = _run(manager, app.format(device=device), events,
                     subscribe=subscribe)
    assert plain == _flat(calls) and len(calls) < len(plain)
    assert sum(seen) == len(plain) and set(seen) == {1}
    bridge = rt.device_bridges[0]
    assert bridge.egress == {"columns": [0, 0],
                             "events": [len(calls), len(plain)]}


RATE_APP = """
define stream S (sym string, price double, vol long);
{device}
@info(name='q')
from S[price > 50.0] select sym, vol output {mode} every 3 events
insert into O;
"""


@pytest.mark.parametrize("mode", ["first", "last"])
def test_an_event_rate_limiter_and_a_query_callback_see_the_chunk(manager,
                                                                  mode):
    events = _s_events(100, seed=3)
    seen = {}

    def subscribe_as(key):
        def subscribe(rt):
            rt.add_query_callback("q", QueryCallback(
                lambda ts, cur, exp: seen.setdefault(key, []).append(
                    (ts, [e.data for e in cur], exp))))
        return subscribe

    _rt, host = _run(manager, RATE_APP.format(device="", mode=mode), events,
                     subscribe=subscribe_as("host"))
    rt, dev = _run(manager, RATE_APP.format(
        device="@device(batch='16', strict='true')", mode=mode), events,
        subscribe=subscribe_as("device"))
    assert _flat(host) and _flat(dev) == _flat(host)
    # the query callback gets what the limiter let through, a chunk a call,
    # stamped with the newest event of the chunk, nothing expired
    assert [r for _ts, cur, _e in seen["device"] for r in cur] == _flat(host)
    assert all(exp is None for _ts, _cur, exp in seen["device"])
    assert [ts for ts, _c, _e in seen["device"]] == [c[-1][0] for c in dev]
    bridge = rt.device_bridges[0]
    assert bridge.egress["events"] == [len(dev), len(_flat(dev))]


def test_a_raising_callback_is_counted_once_and_the_next_batch_arrives(
        manager):
    app, events, device = CASES["stream"]
    rt = manager.create_siddhi_app_runtime(app.format(device=device),
                                           playback=True)
    calls, good = [], []

    def cb(evs):
        calls.append(len(evs))
        if len(calls) == 1:
            raise ValueError("bad subscriber")
        good.extend(e.data for e in evs)

    other = []
    rt.add_callback("O", StreamCallback(cb))
    rt.add_callback("O", StreamCallback(lambda evs: other.extend(
        e.data for e in evs)))
    rt.start()
    for sid, row, ts in events:
        rt.input_handler(sid).send(list(row), timestamp=ts)
    rt.flush_device()
    junction = rt.ctx.stream_junctions["O"]
    assert junction.receiver_errors == 1        # once a chunk, not a row
    assert len(calls) > 2 and calls[0] > 1
    # the other subscriber saw every chunk, the failing one all but its first
    assert len(other) == sum(calls) and good == other[calls[0]:]


@pytest.mark.parametrize("shape", ["events", "columns"])
def test_rows_per_delivery_reads_a_batchs_rows_and_the_waterfall_reconciles(
        manager, shape):
    """The counters on the bridge, the gauges beside the probe's, the
    ``/latency`` report — and ``reconciliation_ratio`` 1.0 on both paths."""
    app = """
    @app(name='egress-%s')
    define stream S (sym string, price double, vol long);
    @info(name='q') @device(batch='32', strict='true', async='true')
    from S select sym, price, vol insert into O;
    """ % shape
    rt = manager.create_siddhi_app_runtime(app, playback=True)
    rt.add_callback("O", StreamCallback(lambda evs: None))
    if shape == "events":
        _with_query_callback(rt)
    rt.start()
    events = _s_events(32 * 5)
    for sid, row, ts in events:
        rt.input_handler(sid).send(list(row), timestamp=ts)
    rt.flush_device()
    bridge = rt.device_bridges[0]
    other = "columns" if shape == "events" else "events"
    assert bridge.egress[shape] == [5, 160] and bridge.egress[other] == [0, 0]
    rep = rt.observability.latency_report()["queries"]["q"]
    assert rep["egress"][shape] == {"deliveries": 5, "rows": 160,
                                    "rows_per_delivery": 32.0}
    assert rep["egress"][other]["rows_per_delivery"] == 0.0
    assert rep["end_to_end"]["count"] == 160
    assert rep["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-5)
    assert {"egress_decode", "sink_publish"} <= set(rep["phases"])
    gauges = rt.ctx.statistics_manager.gauges
    assert gauges[f"device.q.egress_{shape}_deliveries_total"].value == 5
    assert gauges[f"device.q.egress_{shape}_rows_total"].value == 160
    assert gauges[f"device.q.egress_{other}_rows_total"].value == 0


def test_a_per_event_delivery_is_still_a_list_of_one(manager):
    rt = manager.create_siddhi_app_runtime(
        "define stream S (v int);\n"
        "from S[v > 0] select v insert into O;", playback=True)
    calls = []
    rt.add_callback("O", StreamCallback(lambda evs: calls.append(len(evs))))
    rt.start()
    for i in range(1, 6):
        rt.input_handler("S").send([i], timestamp=1000 + i)
    assert calls == [1] * 5


def test_an_event_list_send_reaches_a_stream_callback_as_one_list(manager):
    """The chunk contract is the junction's, not the device tier's: a chunk
    that reaches a stream whole is one ``receive``, CURRENT and EXPIRED
    only, order kept."""
    from siddhi_tpu.core.event import EventType
    from siddhi_tpu.core.stream import _StreamCallbackReceiver
    rt = manager.create_siddhi_app_runtime(
        "define stream S (v int);", playback=True)
    calls = []
    rt.add_callback("S", StreamCallback(lambda evs: calls.append(list(evs))))
    rt.start()
    rt.input_handler("S").send([Event(1000 + i, [i]) for i in range(4)])
    assert [[e.data for e in c] for c in calls] == [[[0], [1], [2], [3]]]
    got = []
    _StreamCallbackReceiver(StreamCallback(got.extend)).receive_chunk([
        StreamEvent(1, [1], EventType.CURRENT),
        StreamEvent(2, [2], EventType.TIMER),
        StreamEvent(3, [3], EventType.EXPIRED),
        StreamEvent(4, [4], EventType.RESET)])
    assert got == [Event(1, [1]), Event(3, [3], True)]
