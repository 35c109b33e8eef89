"""Grouped hopping flush and the selector's tail on it, device against the
scalar interpreter (PR 35): ``from S#window.hopping(D, H) select ... group by
... order by ... limit ...`` is served from the chip: one row per key live
at a boundary, first-seen key order, then ``order by`` / ``offset`` /
``limit`` on that chunk."""

import random

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu import DeviceCompileError, DeviceStreamRuntime
from util_parity import rows_equal

DEFINE = "define stream S (sym string, price double, vol long, id long);\n"
BIG = 2 ** 32 + 12345          # a long key above 2^32 keeps its width


def _rows(n, seed, spread, keys="abcde", ids=(7, BIG, BIG + 1, -5)):
    rng = random.Random(seed)
    ts, out = 1000, []
    for _ in range(n):
        ts += rng.randrange(spread)
        out.append(([rng.choice(keys), round(rng.uniform(0, 50), 2),
                     rng.randrange(100), rng.choice(ids)], ts))
    return out


def _host(app, rows_ts):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback("O", StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    ih = rt.input_handler("S")
    for row, ts in rows_ts:
        ih.send(list(row), timestamp=ts)
    m.shutdown()
    return [e.data for e in got]


def _device(app, rows_ts, batch=64, window=256):
    rt = DeviceStreamRuntime(app, batch_capacity=batch,
                             window_capacity=window)
    got = []
    rt.add_callback(got.extend)
    for row, ts in rows_ts:
        rt.send(list(row), timestamp=ts)
    rt.flush()
    return got, rt


def assert_parity(app, rows_ts, **sizes):
    expected = _host(app, rows_ts)
    actual, rt = _device(app, rows_ts, **sizes)
    assert expected, "the case must emit rows"
    assert len(expected) == len(actual), (len(expected), len(actual),
                                          expected[:5], actual[:5])
    for e, a in zip(expected, actual):
        assert rows_equal(e, a, rel=2e-3, abs_=2e-3), (e, a)
    assert int(rt.state["window_drops"]) == 0
    return rt


def _app(select, group="sym", window="hopping(1 sec, 400)", tail=""):
    return (DEFINE + f"from S#window.{window}\nselect {select}\n"
            f"group by {group}\n{tail}\ninsert into O;\n")


AGGS = {
    "count": "sym, count() as c",
    "sum_long": "sym, sum(vol) as s, count() as c",
    "sum_double": "sym, sum(price) as total",
    "avg": "sym, avg(price) as ap, avg(vol) as av",
    "min_max": "sym, min(price) as lo, max(vol) as hi, count() as c",
    "carried_column": "sym, vol, price, sum(vol) as s",
}


@pytest.mark.parametrize("name", sorted(AGGS))
def test_parity_grouped_hopping_aggregates(name):
    assert_parity(_app(AGGS[name]), _rows(150, 31, 120))


@pytest.mark.parametrize("name", sorted(AGGS))
def test_parity_grouped_hopping_small_batches(name):
    assert_parity(_app(AGGS[name]), _rows(120, 32, 150), batch=8)


def test_parity_two_group_keys_string_and_long():
    assert_parity(_app("sym, id, sum(vol) as s, count() as c",
                       group="sym, id"), _rows(200, 33, 90, keys="ab"))


def test_parity_long_key_above_2_pow_32_is_not_folded():
    # BIG and BIG + 1 differ in their low bits only by one and 7 / -5 share
    # no bits with them: folded to 32 bits BIG would meet 12345
    rows = _rows(160, 34, 100, ids=(BIG, BIG + 2 ** 32, 12345, -BIG))
    assert_parity(_app("id, count() as c, sum(vol) as s", group="id"), rows)


def test_parity_boundary_exactly_at_an_event():
    # first event at 1000 arms 1400; an event AT 1400 fires the boundary
    # before it joins the window, and one at 1800 likewise
    rows = [(["a", 1.0, 1, 1], 1000), (["b", 2.0, 2, 1], 1200),
            (["a", 3.0, 3, 1], 1400), (["b", 4.0, 4, 1], 1401),
            (["c", 5.0, 5, 1], 1800), (["a", 6.0, 6, 1], 2200),
            (["a", 7.0, 7, 1], 2600)]
    assert_parity(_app("sym, sum(vol) as s, count() as c"), rows)


def test_parity_several_boundaries_in_one_batch():
    # hop 40 over gaps up to 30: a batch of 64 events crosses dozens of
    # boundaries, more than flush_cap: the rest are deferred, never dropped
    rt = assert_parity(_app("sym, count() as c, max(vol) as hi",
                            window="hopping(200, 40)"),
                       _rows(300, 35, 30), batch=64, window=128)
    assert rt.compiled.flush_cap == 3


def test_parity_gap_of_many_hops():
    # thousands of whole hops between events: empty windows are skipped by
    # arithmetic, not stepped through
    rows = _rows(40, 36, 400_000)
    rt = assert_parity(_app("sym, count() as c, sum(vol) as s"), rows,
                       batch=4)
    assert rt.compiled.flush_cap == 2


def test_window_overflow_is_counted_as_window_drops():
    # 40 events inside one duration, a window of 16: the boundary reads 16
    # + what the batch holds, the rest were evicted alive and are counted
    rows = [(["a", 1.0, 1, 1], 1000 + i) for i in range(40)] \
        + [(["a", 1.0, 1, 1], 5000)]
    got, rt = _device(_app("sym, count() as c",
                           window="hopping(1 sec, 400)"), rows, batch=8,
                      window=16)
    assert int(rt.state["window_drops"]) > 0
    assert got and got[0][1] < 40


TAILS = {
    "desc": "order by c desc",
    "asc": "order by c asc",
    "two_keys_ties": "order by c desc, s asc",
    "desc_limit_1": "order by c desc limit 1",
    "two_keys_limit_1": "order by c desc, s asc limit 1",
    "asc_limit_1": "order by s limit 1",
    "limit_2": "order by c desc limit 2",
    "limit_only": "limit 2",
    "offset_only": "offset 1",
    "limit_offset": "order by s desc limit 2 offset 1",
    "order_by_double": "order by ap desc limit 3",
}


@pytest.mark.parametrize("name", sorted(TAILS))
def test_parity_selector_tail_on_a_flush_chunk(name):
    # five keys over few events: counts tie all the time, so the stable
    # order (ties keep first-seen key order) is what is compared
    app = _app("sym, count() as c, sum(vol) as s, avg(price) as ap",
               tail=TAILS[name])
    assert_parity(app, _rows(220, 37, 60))
    assert_parity(app, _rows(100, 38, 150), batch=8)


HOT_ITEMS = """
define stream Bid (auction long, bidder long, price long);
from Bid#window.hopping(1000, 200)
select auction, count() as num
group by auction
order by num desc
limit 1
insert into HotItems;
"""


def _bids(n, seed):
    rng = np.random.default_rng(seed)
    auction = (rng.zipf(1.7, n) % 7 + np.arange(n) // 500 * 3
               + 2 ** 33).tolist()
    return [([a, int(rng.integers(0, 1000)), int(rng.integers(100, 10 ** 6))],
             1_000_000 + i) for i, a in enumerate(auction)]


def test_parity_nexmark_q5_hot_items():
    """The deployment's query: 3,000 bids a tick apart, `hopping(1000, 200)`:
    one row a boundary, the auction with the most bids and its count, ties
    to the auction first seen in the window."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(HOT_ITEMS, playback=True)
    expected = []
    rt.add_callback("HotItems", StreamCallback(
        lambda evs: expected.extend(e.data for e in evs)))
    rt.start()
    rows = _bids(3000, 5)
    for row, ts in rows:
        rt.input_handler("Bid").send(list(row), timestamp=ts)
    m.shutdown()
    dev = DeviceStreamRuntime(HOT_ITEMS, batch_capacity=256,
                              window_capacity=1280)
    actual = []
    dev.add_callback(actual.extend)
    for row, ts in rows:
        dev.send(list(row), timestamp=ts)
    dev.flush()
    assert len(expected) == 14 and actual == expected
    assert int(dev.state["window_drops"]) == 0
    assert 0 < int(dev.state["window_live_keys"]) <= 20
    assert int(dev.state["window_held"]) == 1280


REFUSED = {
    "order_by_on_a_sliding_window": (
        DEFINE + "from S#window.length(5) select sym, sum(vol) as s "
        "group by sym order by s insert into O;", "sliding window 'length'"),
    "limit_without_a_window": (
        DEFINE + "from S select sym, vol limit 2 insert into O;",
        "without a window"),
    "order_by_on_an_ungrouped_hopping_flush": (
        DEFINE + "from S#window.hopping(1 sec, 400) select sum(vol) as s "
        "order by s insert into O;", "ungrouped hopping"),
    "grouped_session": (
        DEFINE + "from S#window.session(1 sec) select sym, sum(vol) as s "
        "group by sym insert into O;", "session"),
    "grouped_time_batch_with_limit": (
        DEFINE + "from S#window.timeBatch(1 sec) select sym, sum(vol) as s "
        "group by sym limit 1 insert into O;", "timeBatch"),
    "having_on_a_grouped_hopping_flush": (
        DEFINE + "from S#window.hopping(1 sec, 400) select sym, sum(vol) "
        "as s group by sym having s > 10 insert into O;", "having"),
    "stddev_on_a_grouped_hopping_flush": (
        DEFINE + "from S#window.hopping(1 sec, 400) select sym, "
        "stdDev(price) as sd group by sym insert into O;", "stdDev"),
    "order_by_a_string": (
        DEFINE + "from S#window.hopping(1 sec, 400) select sym, sum(vol) "
        "as s group by sym order by sym insert into O;", "non-numeric"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_still_keeps_the_host_path_is_refused_by_name(name):
    app, says = REFUSED[name]
    with pytest.raises(DeviceCompileError, match=says):
        DeviceStreamRuntime(app)


# ---------------------------------------------------------------------------
# the served path: SiddhiManager -> try_build_device_query -> the bridge
# ---------------------------------------------------------------------------

SERVED = HOT_ITEMS.replace(
    "from Bid#", "@device(strict='true', batch='256', window='1280'{more})\n"
    "from Bid#")


def _serve(app, rows, subscribe=None):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app, playback=True)
        got = []
        rt.add_callback("HotItems", StreamCallback(
            lambda evs: got.extend(e.data for e in evs)))
        rt.start()
        if subscribe is not None:
            subscribe(rt)
        ih = rt.input_handler("Bid")
        n = len(rows)
        cols = {name: np.array([r[0][j] for r in rows], dtype=np.int64)
                for j, name in enumerate(("auction", "bidder", "price"))}
        ts = np.array([t for _, t in rows], dtype=np.int64)
        for s in range(0, n, 100):      # chunks that straddle batches
            ih.send_columns({k: v[s:s + 100] for k, v in cols.items()},
                            ts[s:s + 100])
        rt.flush_device()
        return rt, got, m
    except BaseException:
        m.shutdown()
        raise


@pytest.mark.parametrize("more", ["", ", async='true'"],
                         ids=["sync", "async"])
def test_the_deployment_is_served_strict_with_one_bridge_and_no_host_tier(
        more):
    rows = _bids(3000, 5)
    rt, got, m = _serve(SERVED.format(more=more), rows)
    try:
        assert len(rt.device_bridges) == 1
        assert not (rt.host_bridges or rt.fleet_bridges or rt.query_runtimes
                    or rt.partition_runtimes)
        bridge = rt.device_bridges[0]
        assert bridge.guard.report()["failures"] == 0
        # a bid a tick: no batch spans more boundaries than a step
        # resolves, so only the first batch is serial and the async driver
        # keeps its window of two
        assert bridge.runtime.step_gauges["hop_serial_batches"] == 1
        if more:
            assert bridge.driver.window == 2
        dev = DeviceStreamRuntime(HOT_ITEMS, batch_capacity=256,
                                  window_capacity=1280)
        alone = []
        dev.add_callback(alone.extend)
        for row, ts in rows:
            dev.send(list(row), timestamp=ts)
        dev.flush()
        assert len(got) == 14 and got == alone
        # the window's gauges, read at drain points, and the two nested
        # trackers, in /latency and in the statistics manager
        assert bridge.runtime.step_gauges["window_fill_share"] == 1.0
        assert 0 < bridge.runtime.step_gauges["window_live_keys"] <= 20
        entry = rt.observability.latency_report()["queries"][
            bridge.query_name]
        assert entry["step"] == bridge.runtime.step_gauges
        assert {"hop_drain", "hop_flush", "egress_decode"} <= set(
            entry["phases"])
        trackers = bridge.probe.phases.trackers
        assert trackers["hop_drain"].count == trackers["egress_decode"].count
        # 14 boundaries in 12 batches of 256: most batches fire one
        assert 0 < trackers["hop_flush"].count \
            <= trackers["egress_decode"].count
        report = rt.ctx.statistics_manager.report()
        assert any(k.endswith(".window_live_keys")
                   for section in report.values() if isinstance(section, dict)
                   for k in section)
    finally:
        m.shutdown()


def test_the_guards_shadow_replays_this_query_through_the_host_selector():
    """A collect that fails is replayed by the DeviceGuard through the host
    ``QueryRuntime``, whose selector IS the semantics: the replayed batch's
    rows are ordered and limited there (one row a flush, the top auction
    among the batch's own events: the host replay starts its window empty),
    and every other row is the device's."""
    rows = _bids(3000, 5)

    def sabotage(rt):
        compiled = rt.device_bridges[0].runtime.compiled
        inner, calls = compiled.decode_outputs, [0]

        def decode(out):
            calls[0] += 1
            if calls[0] == 3:           # the third batch's collect fails
                raise RuntimeError("sabotaged decode")
            return inner(out)

        compiled.decode_outputs = decode

    _rt0, sound, m0 = _serve(SERVED.format(more=""), rows)
    m0.shutdown()
    rt, got, m = _serve(SERVED.format(more=""), rows, subscribe=sabotage)
    try:
        guard = rt.device_bridges[0].guard
        assert guard.failures == 1 and guard.fallback_events == 256
        assert guard.report()["fallback_engine"] == "scalar"
        # batch 3 is events 512..767: the device's boundary at event 600
        # was lost with the decode; the replay arms its own at 512 + 200
        auction = np.array([r[0][0] for r in rows[512:712]])
        keys, first, counts = np.unique(auction, return_index=True,
                                        return_counts=True)
        tied = np.flatnonzero(counts == counts.max())
        top = tied[np.argmin(first[tied])]
        # the device's other rows are all there, in order; what is extra
        # came from the host replay: its window holds the 256 replayed
        # events until they age out, a flush chunk a boundary, each ordered
        # and limited to ONE row by the host selector
        kept, extra = sound[:2] + sound[3:], []
        for row in got:
            if kept and row == kept[0]:
                kept.pop(0)
            else:
                extra.append(row)
        assert not kept and 1 <= len(extra) <= 1 + 1000 // 200
        assert extra[0] == [int(keys[top]), int(counts[top])]
        in_batch = dict(zip(*np.unique(
            [r[0][0] for r in rows[512:768]], return_counts=True)))
        assert all(c <= in_batch[a] for a, c in extra)
    finally:
        m.shutdown()


@pytest.mark.parametrize("name", ["order_by_on_a_sliding_window",
                                  "grouped_session",
                                  "order_by_on_an_ungrouped_hopping_flush"])
def test_strict_deployment_refuses_what_keeps_the_host_path(name):
    app, says = REFUSED[name]
    app = app.replace("from S#", "@device(strict='true')\nfrom S#")
    m = SiddhiManager()
    try:
        with pytest.raises(DeviceCompileError, match=says):
            m.create_siddhi_app_runtime(app, playback=True)
        # without `strict` the same text falls back to the interpreter
        rt = m.create_siddhi_app_runtime(
            app.replace("strict='true'", "batch='8'"), playback=True)
        assert not rt.device_bridges and rt.query_runtimes
    finally:
        m.shutdown()


def test_trailing_filtered_events_fire_boundaries_on_the_streams_clock():
    """The interpreter's boundary timer runs on the playback clock, which a
    filtered event advances too: boundaries after the last ACCEPTED event
    fire (and, once the window has aged out, are skipped without a step)."""
    rows = [(["a", 1.0, 5, 1], 1000 + 10 * i) for i in range(30)] \
        + [(["b", 1.0, 500, 1], 1300 + 900 * i) for i in range(40)]
    app = (DEFINE + "from S[vol < 100]#window.hopping(1 sec, 400)\n"
           "select sym, count() as c group by sym insert into O;\n")
    rt = assert_parity(app, rows, batch=8)
    assert int(rt.state["hop_next"]) > int(rt.state["last_ts"])
