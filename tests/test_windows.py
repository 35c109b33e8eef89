"""Window processor behavioral tests (reference: ``core/query/window/`` suites)."""

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback


@pytest.fixture
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def collect(manager, app, out="O"):
    rt = manager.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback(out, StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    return rt, got


def test_length_window_sliding_sum(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.length(3) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    for i, v in enumerate([10, 20, 30, 40, 50]):
        ih.send([v], timestamp=100 + i)
    assert [e.data[0] for e in got] == [10, 30, 60, 90, 120]


def test_length_batch_window(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.lengthBatch(3) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    for i, v in enumerate([1, 2, 3, 4, 5, 6]):
        ih.send([v], timestamp=100 + i)
    # aggregated batch chunks collapse to ONE row per flush (reference
    # QuerySelector.processInBatchNoGroupBy — lengthBatchWindowTest4 asserts
    # a single 100.0 row for a 4-event batch)
    assert [e.data[0] for e in got] == [6, 15]


def test_time_window_expiry(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.time(100) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([10], timestamp=1000)
    ih.send([20], timestamp=1050)
    ih.send([30], timestamp=1200)   # both prior events expired
    assert [e.data[0] for e in got] == [10, 30, 30]


def test_time_batch_window(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.timeBatch(100) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([1], timestamp=1000)
    ih.send([2], timestamp=1050)
    ih.send([3], timestamp=1120)    # crosses boundary at 1100 → flush batch 1
    ih.send([4], timestamp=1130)
    rt.advance_time(1300)           # flush batch 2 by timer
    sums = [e.data[0] for e in got]
    # one aggregated row per closed bucket (reference batch-mode selector)
    assert sums == [3, 7]


def test_time_length_window(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.timeLength(1000, 2) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([1], timestamp=0)
    ih.send([2], timestamp=10)
    ih.send([4], timestamp=20)      # length 2 exceeded → 1 evicted
    assert [e.data[0] for e in got] == [1, 3, 6]


def test_external_time_window(manager):
    rt, got = collect(manager, """
        define stream S (ts long, v long);
        from S#window.externalTime(ts, 100) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([1000, 10], timestamp=1)
    ih.send([1050, 20], timestamp=2)
    ih.send([1200, 30], timestamp=3)
    assert [e.data[0] for e in got] == [10, 30, 30]


def test_external_time_batch_window(manager):
    rt, got = collect(manager, """
        define stream S (ts long, v long);
        from S#window.externalTimeBatch(ts, 100) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([1000, 1], timestamp=1)
    ih.send([1050, 2], timestamp=2)
    ih.send([1120, 3], timestamp=3)
    ih.send([1230, 4], timestamp=4)   # event 4's batch never flushes (no later event)
    assert [e.data[0] for e in got] == [3, 3]


def test_session_window(manager):
    rt, got = collect(manager, """
        define stream S (k string, v long);
        from S#window.session(100, k) select k, sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send(["a", 1], timestamp=1000)
    ih.send(["a", 2], timestamp=1050)
    ih.send(["a", 5], timestamp=1300)   # previous session closed at 1150
    # session close retracts events 1,2 → sum back to 0, then 5
    assert [e.data for e in got] == [["a", 1], ["a", 3], ["a", 5]]


def test_batch_window(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.batch() select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    from siddhi_tpu import Event
    ih.send([Event(100, [1]), Event(100, [2])])
    ih.send([Event(101, [10])])
    assert [e.data[0] for e in got] == [3, 10]


def test_delay_window(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.delay(100) select v insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([1], timestamp=1000)
    assert got == []
    rt.advance_time(1150)
    assert [e.data[0] for e in got] == [1]


def test_sort_window(manager):
    rt, got = collect(manager, """
        define stream S (v int);
        from S#window.sort(2, v) select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([5], timestamp=1)
    ih.send([3], timestamp=2)
    ih.send([4], timestamp=3)   # keeps 2 smallest (asc): [3,4], evicts 5 (expired)
    assert [e.data[0] for e in got] == [5, 8, 12]


def test_frequent_window(manager):
    rt, got = collect(manager, """
        define stream S (s string);
        from S#window.frequent(1, s) select s, count() as c insert into O;
    """)
    ih = rt.input_handler("S")
    for i, s in enumerate(["a", "a", "b", "a"]):
        ih.send([s], timestamp=i)
    # 'b' displaces nothing (decrements a to 1); only tracked items emit
    data = [e.data for e in got]
    assert data[0] == ["a", 1] and data[1] == ["a", 2]


def test_named_window_shared(manager):
    rt = manager.create_siddhi_app_runtime("""
        define stream S (v long);
        define window W (v long) length(2) output all events;
        from S insert into W;
        from W select sum(v) as total insert into O;
    """, playback=True)
    got = []
    rt.add_callback("O", StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    ih = rt.input_handler("S")
    for i, v in enumerate([1, 2, 4]):
        ih.send([v], timestamp=100 + i)
    # sliding window of 2: sums 1, 3, then expired(1) retracts and 4 arrives → 6
    assert [e.data[0] for e in got] == [1, 3, 6]


def test_cron_window(manager):
    rt, got = collect(manager, """
        define stream S (v long);
        from S#window.cron('*/2 * * * * ?') select sum(v) as total insert into O;
    """)
    ih = rt.input_handler("S")
    ih.send([1], timestamp=0)
    ih.send([2], timestamp=500)
    rt.advance_time(2500)    # cron fires at 2000
    assert [e.data[0] for e in got] == [3]


def test_expression_window_incremental_aggregates_scale():
    """sum() over the buffer is O(1) amortized per event, not O(n): five
    times the events take about five times as long, where O(n) an event
    would take twenty-five. One run is held against another, the fastest
    of three each, never against a fixed time."""
    import time

    def seconds(n):
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime("""
define stream S (v long);
from S#window.expression('sum(v) <= 100000000') select v insert into O;
""", playback=True)
        rt.start()
        h = rt.input_handler("S")
        t0 = time.perf_counter()
        for i in range(n):
            h.send([1], timestamp=1000 + i)
        dt = time.perf_counter() - t0
        m.shutdown()
        return dt

    small = min(seconds(4_000) for _ in range(3))
    large = min(seconds(20_000) for _ in range(3))
    assert large < 15 * small
