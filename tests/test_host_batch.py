"""Columnar host fast-path parity: the vectorized micro-batch engine
(``@app:host_batch`` → ``tpu/host_exec.py``) vs the scalar interpreter.

Every app runs twice over identical data: once per-event through the plain
interpreter (the semantic oracle), once chunked through the columnar engine
at several chunk sizes — including chunk=1 (per-event staging) and odd sizes
that straddle micro-batch boundaries. Outputs compare as order-insensitive
multisets with f64-scale tolerance (``util_parity``).

Also covers: per-query fallback mixes (one lowering + one interpreter query
in the same app), the DeviceGuard quarantine fallback engine, snapshot/
restore of columnar state, and host_batch metrics.
"""

import random

import pytest

from util_parity import assert_rows_match

from siddhi_tpu import SiddhiManager, StreamCallback

STREAM = "define stream S (sym string, v double, n long);\n"
HB = "@app:host_batch(batch='128', lanes='4')\n"


@pytest.fixture()
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def gen_events(n, seed=0, syms=4, ts_step=7):
    rng = random.Random(seed)
    out = []
    ts = 1_000_000
    for i in range(n):
        out.append(([f"s{rng.randrange(syms)}",
                     round(rng.uniform(0.0, 100.0), 3),
                     rng.randrange(1000)], ts))
        ts += rng.randrange(1, ts_step)
    return out


def run_scalar(manager, app_text, events, out_streams=("Out",)):
    rt = manager.create_siddhi_app_runtime(app_text, playback=True)
    got = {o: [] for o in out_streams}
    for o in out_streams:
        rt.add_callback(o, StreamCallback(
            lambda evs, o=o: got[o].extend(list(e.data) for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    for row, ts in events:
        ih.send(row, timestamp=ts)
    rt.shutdown()
    return got


def run_columnar(manager, app_text, events, chunk, out_streams=("Out",),
                 expect_bridges=None):
    rt = manager.create_siddhi_app_runtime(HB + app_text, playback=True)
    if expect_bridges is not None:
        assert len(rt.host_bridges) == expect_bridges, \
            [b.query_name for b in rt.host_bridges]
    got = {o: [] for o in out_streams}
    for o in out_streams:
        rt.add_callback(o, StreamCallback(
            lambda evs, o=o: got[o].extend(list(e.data) for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    rows = [row for row, _ in events]
    tss = [ts for _, ts in events]
    for i in range(0, len(rows), chunk):
        ih.send_rows(rows[i:i + chunk], tss[i:i + chunk])
    rt.shutdown()                 # finalize drains the open micro-batch
    return got, rt


def check_parity(manager, app_text, events, chunks=(1, 37, 256),
                 out_streams=("Out",), expect_bridges=1):
    ref = run_scalar(manager, app_text, events, out_streams)
    for chunk in chunks:
        got, _rt = run_columnar(manager, app_text, events, chunk,
                                out_streams, expect_bridges=expect_bridges)
        for o in out_streams:
            assert_rows_match(ref[o], got[o])
    return ref


# ---------------------------------------------------------------------------
# stream queries
# ---------------------------------------------------------------------------

def test_filter_projection_parity(manager):
    app = STREAM + """
        from S[v > 50.0 and sym == 's1']
        select sym, v, v * 2.0 as d, n + 1 as m insert into Out;
    """
    ref = check_parity(manager, app, gen_events(700, seed=1))
    assert ref["Out"]                       # non-trivial corpus

def test_running_aggregates_parity(manager):
    app = STREAM + """
        from S select sym, sum(v) as s, count() as c, avg(v) as a,
                      min(v) as mn, max(n) as mx insert into Out;
    """
    check_parity(manager, app, gen_events(500, seed=2))


def test_group_by_parity(manager):
    app = STREAM + """
        from S select sym, sum(v) as s, count() as c, min(n) as mn,
                      max(v) as mx group by sym insert into Out;
    """
    check_parity(manager, app, gen_events(600, seed=3, syms=7))


def test_group_by_two_keys_parity(manager):
    app = STREAM + """
        from S select sym, n, sum(v) as s, count() as c
        group by sym, n insert into Out;
    """
    check_parity(manager, app, gen_events(400, seed=4, syms=3))


def test_length_window_parity(manager):
    app = STREAM + """
        from S#window.length(50)
        select v, sum(v) as s, avg(v) as a, max(v) as mx, count() as c
        insert into Out;
    """
    check_parity(manager, app, gen_events(500, seed=5))


def test_time_window_parity(manager):
    app = STREAM + """
        from S#window.time(300)
        select v, sum(v) as s, count() as c, min(v) as mn insert into Out;
    """
    check_parity(manager, app, gen_events(600, seed=6))


def test_having_parity(manager):
    app = STREAM + """
        from S#window.length(20) select sym, sum(v) as s
        having s > 800.0 insert into Out;
    """
    check_parity(manager, app, gen_events(400, seed=7))


# ---------------------------------------------------------------------------
# patterns
# ---------------------------------------------------------------------------

def test_pattern_chain_parity(manager):
    app = STREAM + """
        from every e1=S[v > 75.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v]
        within 200
        select e1.v as a, e2.v as b, e3.v as c insert into Out;
    """
    ref = check_parity(manager, app, gen_events(800, seed=8))
    assert ref["Out"]                       # chains actually fired


def test_pattern_string_binding_parity(manager):
    app = STREAM + """
        from every e1=S[v > 70.0] -> e2=S[sym == e1.sym and v > e1.v]
        within 400
        select e1.sym as k, e1.v as a, e2.v as b insert into Out;
    """
    ref = check_parity(manager, app, gen_events(700, seed=9, syms=3))
    assert ref["Out"]


def test_sequence_parity(manager):
    app = STREAM + """
        from every e1=S[v > 60.0], e2=S[v > e1.v]
        select e1.v as a, e2.v as b insert into Out;
    """
    ref = check_parity(manager, app, gen_events(500, seed=10))
    assert ref["Out"]


def test_partitioned_pattern_parity(manager):
    app = STREAM + """
        partition with (sym of S)
        begin
        from every e1=S[v > 60.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v]
        within 300
        select e1.sym as k, e1.v as a, e2.v as b, e3.v as c
        insert into Out;
        end;
    """
    ref = check_parity(manager, app, gen_events(900, seed=11, syms=6))
    assert ref["Out"]


def test_partitioned_pattern_batch_straddle(manager):
    # chains MUST complete across micro-batch boundaries: tiny odd chunks
    app = STREAM + """
        partition with (sym of S)
        begin
        from every e1=S[v > 50.0] -> e2=S[v > e1.v]
        within 500
        select e1.sym as k, e1.v as a, e2.v as b insert into Out;
        end;
    """
    events = gen_events(600, seed=12, syms=2)
    ref = run_scalar(manager, app, events)
    assert ref["Out"]
    for chunk in (1, 3, 11, 64):
        got, _ = run_columnar(manager, app, events, chunk)
        assert_rows_match(ref["Out"], got["Out"])


# ---------------------------------------------------------------------------
# fallback mixes / engine selection
# ---------------------------------------------------------------------------

def test_fallback_mix_per_query(manager):
    # query 1 lowers; query 2 (order by) keeps the scalar interpreter —
    # BOTH stay correct inside one app (per-query fallback, not per-app)
    app = STREAM + """
        from S[v > 40.0] select sym, v insert into Out;
        from S#window.lengthBatch(10) select sym, v
        order by v insert into Out2;
    """
    events = gen_events(300, seed=13)
    ref = run_scalar(manager, app, events, out_streams=("Out", "Out2"))
    got, rt = run_columnar(manager, app, events, 37,
                           out_streams=("Out", "Out2"), expect_bridges=1)
    assert [b.kind for b in rt.host_bridges] == ["host_stream"]
    assert_rows_match(ref["Out"], got["Out"])
    assert_rows_match(ref["Out2"], got["Out2"])


def test_unsupported_constructs_keep_interpreter(manager):
    # stdDev (no columnar kernel) and joins must fall back, not break
    app = STREAM + """
        define stream T (sym string, w double);
        from S select sym, stdDev(v) as sd insert into Out;
    """
    events = gen_events(200, seed=14)
    ref = run_scalar(manager, app, events)
    got, rt = run_columnar(manager, app, events, 50, expect_bridges=0)
    assert_rows_match(ref["Out"], got["Out"])


def test_strict_annotation_raises(manager):
    from siddhi_tpu.tpu.expr_compile import DeviceCompileError
    with pytest.raises(DeviceCompileError):
        manager.create_siddhi_app_runtime(STREAM + """
            @host_batch(strict='true')
            from S select sym, stdDev(v) as sd insert into Out;
        """, playback=True)


def test_device_annotation_wins_over_host_batch(manager):
    rt = manager.create_siddhi_app_runtime(HB + STREAM + """
        @device(batch='64')
        from S[v > 10.0] select sym, v insert into Out;
    """, playback=True)
    assert len(rt.device_bridges) == 1
    assert len(rt.host_bridges) == 0


# ---------------------------------------------------------------------------
# runtime integration
# ---------------------------------------------------------------------------

def test_snapshot_restore_columnar_state(manager):
    app = STREAM + """
        from S#window.length(30) select v, sum(v) as s insert into Out;
    """
    events = gen_events(200, seed=15)
    ref = run_scalar(manager, app, events)

    rt = manager.create_siddhi_app_runtime(HB + app, playback=True)
    got = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: got.extend(list(e.data) for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    rows = [r for r, _ in events]
    tss = [t for _, t in events]
    ih.send_rows(rows[:100], tss[:100])
    blob = rt.snapshot()
    rt.shutdown()

    rt2 = manager.create_siddhi_app_runtime(HB + app, playback=True)
    got2 = []
    rt2.add_callback("Out", StreamCallback(
        lambda evs: got2.extend(list(e.data) for e in evs)))
    rt2.start()
    rt2.restore(blob)
    rt2.input_handler("S").send_rows(rows[100:], tss[100:])
    rt2.shutdown()
    # first 100 rows from the original run + the restored continuation must
    # equal the uninterrupted oracle
    assert_rows_match(ref["Out"], got + got2)


def test_host_batch_metrics_registered(manager):
    app = STREAM + "from S[v > 10.0] select sym, v insert into Out;\n"
    rt = manager.create_siddhi_app_runtime(HB + app, playback=True)
    rt.start()
    ih = rt.input_handler("S")
    events = gen_events(300, seed=16)
    rows = [row for row, _ in events]
    tss = [ts for _, ts in events]
    for i in range(0, len(rows), 64):
        ih.send_rows(rows[i:i + 64], tss[i:i + 64])
    rt.flush_host()
    b = rt.host_bridges[0]
    assert b.events_in == 300
    assert b.batches >= 1
    sm = rt.ctx.statistics_manager
    tr = sm.latency.get(f"host_batch.{b.query_name}.step")
    assert tr is not None and tr.count == b.batches
    assert b.report()["engine"] == "columnar"
    # shutdown tears the bridge's metric families down through
    # StatisticsManager.unregister — no dead gauges left behind
    rt.shutdown()
    snap = sm.snapshot_trackers()
    assert not any(k.startswith(f"host_batch.{b.query_name}")
                   for d in snap.values() for k in d)


def test_mixed_single_and_chunk_sends(manager):
    # trickle sends stage; a later chunk (and shutdown) drains — state is
    # coherent across both ingress shapes
    app = STREAM + """
        from S select sym, count() as c insert into Out;
    """
    events = gen_events(150, seed=17)
    ref = run_scalar(manager, app, events)
    rt = manager.create_siddhi_app_runtime(HB + app, playback=True)
    got = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: got.extend(list(e.data) for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    for row, ts in events[:50]:
        ih.send(row, timestamp=ts)          # per-event staging
    ih.send_rows([r for r, _ in events[50:]],
                 [t for _, t in events[50:]])
    rt.shutdown()
    assert_rows_match(ref["Out"], got)


def test_quarantine_fallback_uses_columnar_engine(manager):
    # DeviceGuard shadow replay: the quarantined device query reroutes
    # through the COLUMNAR host engine (not the scalar interpreter)
    rt = manager.create_siddhi_app_runtime("""
        @app:chaos(seed='3', device.fail.p='1.0')
        @app:resilience(device.circuit.threshold='2',
                        device.circuit.cooldown.ms='40')
        define stream S (v long);
        @device(batch='2', strict='true')
        from S select v * 2 as d insert into O;
    """, playback=True)
    got = []
    rt.add_callback("O", StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    ih = rt.input_handler("S")
    for i in range(4):
        ih.send([i], timestamp=1000 + i)
    guard = rt.device_bridges[0].guard
    assert guard.fallback_events == 4
    assert guard.report()["fallback_engine"] == "columnar"
    assert sorted(e.data[0] for e in got) == [0, 2, 4, 6]
    rt.shutdown()


def test_multi_stream_pattern_single_stream_chunks(manager):
    # chunked ingress arrives PER JUNCTION, so a multi-stream pattern's
    # micro-batches routinely carry only one stream's events — the absent
    # stream's columns must still exist (review finding: emit skipped them
    # and the whole chunk was silently dropped via receiver error isolation)
    app = """
        define stream A (v double);
        define stream B (w double);
        from every e1=A[v > 10.0] -> e2=B[w > e1.v]
        select e1.v as a, e2.w as b insert into Out;
    """
    ref = {}
    for columnar in (False, True):
        rt = manager.create_siddhi_app_runtime(
            (HB if columnar else "") + app, playback=True)
        got = []
        rt.add_callback("Out", StreamCallback(
            lambda evs: got.extend(list(e.data) for e in evs)))
        rt.start()
        if columnar:
            assert len(rt.host_bridges) == 1
            rt.input_handler("A").send_rows([[12.0], [30.0]], [100, 101])
            rt.input_handler("B").send_rows([[20.0], [35.0]], [102, 103])
        else:
            rt.input_handler("A").send([12.0], timestamp=100)
            rt.input_handler("A").send([30.0], timestamp=101)
            rt.input_handler("B").send([20.0], timestamp=102)
            rt.input_handler("B").send([35.0], timestamp=103)
        rt.shutdown()
        ref[columnar] = got
    assert ref[True] and ref[True] == ref[False]


def test_send_rows_length_mismatch_raises(manager):
    rt = manager.create_siddhi_app_runtime(
        HB + STREAM + "from S select sym insert into Out;", playback=True)
    rt.start()
    with pytest.raises(ValueError, match="timestamps"):
        rt.input_handler("S").send_rows([["a", 1.0, 1], ["b", 2.0, 2]], [1])
    rt.shutdown()


# ---------------------------------------------------------------------------
# randomized parity fuzz
# ---------------------------------------------------------------------------

_FUZZ_TEMPLATES = [
    "from S[v > {t:.1f}] select sym, v, n insert into Out;",
    "from S[v > {t:.1f}] select sym, sum(v) as s, count() as c "
    "group by sym insert into Out;",
    "from S#window.length({n}) select v, sum(v) as s, min(v) as mn "
    "insert into Out;",
    "from S#window.time({ms}) select v, count() as c, max(v) as mx "
    "insert into Out;",
    "from every e1=S[v > {t:.1f}] -> e2=S[v > e1.v] within {ms} "
    "select e1.v as a, e2.v as b insert into Out;",
]


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_parity(manager, seed):
    rng = random.Random(100 + seed)
    tmpl = _FUZZ_TEMPLATES[seed % len(_FUZZ_TEMPLATES)]
    app = STREAM + tmpl.format(t=rng.uniform(20, 80),
                               n=rng.choice([5, 17, 60]),
                               ms=rng.choice([50, 300, 900]))
    events = gen_events(rng.randrange(200, 500), seed=seed * 7,
                        syms=rng.choice([2, 5, 9]))
    chunk = rng.choice([1, 13, 100, 400])
    ref = run_scalar(manager, app, events)
    got, _ = run_columnar(manager, app, events, chunk, expect_bridges=1)
    assert_rows_match(ref["Out"], got["Out"])
