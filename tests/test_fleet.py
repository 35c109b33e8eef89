"""Query-fleet subsystem: multi-tenant shared compilation + cross-app lanes.

Oracle parity of fleet-batched execution (``@app:fleet`` →
``siddhi_tpu/fleet/``) against per-app solo runtimes over identical data:
filters with per-tenant constants (numeric + string), running and group-by
aggregates, length/time windows with per-tenant sizes, patterns/sequences
with per-tenant thresholds and within horizons, partitioned patterns.
Plus: the 64-homogeneous-tenants ≤2-compiled-programs-per-backend pin,
tenant isolation under snapshot/restore, plan-cache eviction, fallback
mixes (one non-normalizing tenant must not poison the fleet), fleet.*
metrics and their unregister-on-shutdown, the same-app host_bridge plan
dedupe, and the shape-key lint (scripts/check_fleet_shapes.py).
"""

import os
import random
import subprocess
import sys

import pytest

from util_parity import assert_rows_match

from siddhi_tpu import SiddhiManager, StreamCallback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FLEET = "@app:fleet(batch='96', lanes='4')\n"
STREAM = "define stream S (sym string, v double, n long);\n"


@pytest.fixture()
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def gen_events(n, seed=0, syms=5, ts_step=40):
    rng = random.Random(seed)
    out, ts = [], 1_000_000
    for i in range(n):
        out.append(([f"s{rng.randrange(syms)}",
                     round(rng.uniform(0.0, 100.0), 3),
                     rng.randrange(1000)], ts))
        ts += rng.randrange(1, ts_step)
    return out


def run_tenants(manager, apps_text, events, out_stream="Out",
                expect_fleet=None, chunk=None):
    """Build K tenant apps, feed every one the same events (per-event sends
    or chunked ``send_rows``), return per-tenant output rows."""
    runtimes, got = [], []
    for text in apps_text:
        rt = manager.create_siddhi_app_runtime(text, playback=True)
        rows = []
        rt.add_callback(out_stream, StreamCallback(
            lambda evs, rows=rows: rows.extend(list(e.data) for e in evs)))
        rt.start()
        runtimes.append(rt)
        got.append(rows)
    if expect_fleet is not None:
        engaged = sum(len(rt.fleet_bridges) for rt in runtimes)
        assert engaged == expect_fleet, \
            f"fleet engaged {engaged}, expected {expect_fleet}"
    if chunk:
        rows_all = [row for row, _ in events]
        tss = [ts for _, ts in events]
        for s in range(0, len(events), chunk):
            for rt in runtimes:
                rt.input_handler("S").send_rows(
                    [list(r) for r in rows_all[s:s + chunk]],
                    list(tss[s:s + chunk]))
    else:
        for row, ts in events:
            for rt in runtimes:
                rt.input_handler("S").send(list(row), timestamp=ts)
    for rt in runtimes:
        rt.flush_host()
    return runtimes, got


def tenant_apps(body_fn, k, ann=FLEET, name="t"):
    return [f"@app(name='{name}{i}')\n{ann}{STREAM}{body_fn(i)}"
            for i in range(k)]


def parity(manager, body_fn, k=4, n=400, out="Out", chunk=7, seed=0,
           expect_fleet=None):
    """Fleet vs solo-scalar over identical data, per tenant."""
    events = gen_events(n, seed=seed)
    _, fleet = run_tenants(manager, tenant_apps(body_fn, k), events,
                           out_stream=out, expect_fleet=expect_fleet,
                           chunk=chunk)
    solo_mgr = SiddhiManager()
    try:
        _, solo = run_tenants(solo_mgr,
                              tenant_apps(body_fn, k, ann="", name="u"),
                              events, out_stream=out)
    finally:
        solo_mgr.shutdown()
    for i in range(k):
        assert_rows_match(solo[i], fleet[i])
    return fleet


# ---------------------------------------------------------------------------
# oracle parity
# ---------------------------------------------------------------------------

def test_filter_parity_per_tenant_constants(manager):
    parity(manager, lambda i:
           f"from S[v > {10.0 + 7 * i} and n < {900 - i}] "
           f"select sym, v, n insert into Out;", expect_fleet=4)
    assert manager.fleet.stats()["cache"]["misses"] == 1


def test_filter_string_param_parity(manager):
    parity(manager, lambda i:
           f"from S[sym == 's{i}' and v > {5.0 + i}] "
           f"select v, n * {i + 2} as nn insert into Out;", k=4)


def test_projection_math_and_having_parity(manager):
    parity(manager, lambda i:
           f"from S select sym, sum(v) as s group by sym "
           f"having s > {50.0 + 20 * i} insert into Out;", k=3)


def test_running_aggregate_parity(manager):
    parity(manager, lambda i:
           f"from S[v > {2.0 + i}] select sum(v) as s, count() as c, "
           f"min(n) as mn insert into Out;", k=3)


def test_group_by_parity(manager):
    parity(manager, lambda i:
           f"from S[v < {95.0 - i}] select sym, sum(n) as s, avg(v) as a "
           f"group by sym insert into Out;", k=3)


def test_length_window_per_tenant_sizes(manager):
    # window SIZE differs per tenant — sizes are runtime overrides of one
    # shared plan, so all tenants still share one compile
    parity(manager, lambda i:
           f"from S#window.length({4 + 3 * i}) select avg(v) as a, "
           f"max(n) as m insert into Out;", k=4, expect_fleet=4)
    assert manager.fleet.stats()["cache"]["misses"] == 1


def test_time_window_per_tenant_sizes(manager):
    parity(manager, lambda i:
           f"from S#window.time({200 + 100 * i}) select sum(v) as s "
           f"insert into Out;", k=3)


def test_pattern_parity_per_tenant_within(manager):
    parity(manager, lambda i:
           f"from every e1=S[v > {80.0 + i}] -> e2=S[v > e1.v] "
           f"within {3000 + 700 * i} "
           f"select e1.v as a, e2.v as b, e2.n as n insert into Out;",
           k=4, expect_fleet=4)
    assert manager.fleet.stats()["cache"]["misses"] == 1


def test_sequence_parity(manager):
    parity(manager, lambda i:
           f"from every e1=S[v > {85.0 + i}], e2=S[v > e1.v] "
           f"select e1.v as a, e2.v as b insert into Out;", k=3)


def test_partitioned_pattern_parity(manager):
    parity(manager, lambda i:
           f"partition with (sym of S) begin "
           f"from every e1=S[v > {70.0 + 2 * i}] -> e2=S[v > e1.v] "
           f"within {2000 + 500 * i} "
           f"select e1.v as a, e2.v as b insert into Out; end;",
           k=3, expect_fleet=3)
    assert manager.fleet.stats()["cache"]["misses"] == 1


def test_per_event_sends_parity(manager):
    parity(manager, lambda i:
           f"from S[v > {30.0 + i}] select sym, v insert into Out;",
           k=3, n=150, chunk=None)


# ---------------------------------------------------------------------------
# the 64-tenant shared-compilation pin (acceptance criterion)
# ---------------------------------------------------------------------------

def test_64_homogeneous_tenants_share_two_programs_per_backend(manager):
    k = 64
    events = gen_events(240, seed=3)

    def body(i):
        return (f"@info(name='rule') from S[v > {20.0 + i * 0.5}] "
                f"select sym, v * {1.0 + i * 0.01} as x insert into Out;\n"
                f"@info(name='pat') from every e1=S[v > {88.0 + i * 0.05}] "
                f"-> e2=S[v > e1.v] within {4000 + i} "
                f"select e1.v as a, e2.v as b insert into P;")

    runtimes, fleet_rows = run_tenants(
        manager, tenant_apps(body, k), events, out_stream="Out",
        expect_fleet=2 * k, chunk=16)
    stats = manager.fleet.stats()
    # ≤ 2 compiled programs on the columnar backend for 64x2 queries
    assert stats["cache"]["per_backend"]["numpy"] == 2, stats["cache"]
    assert stats["cache"]["misses"] == 2
    assert stats["members"] == 2 * k
    # ... and they ran batched in one stepped program per shape
    for g in stats["groups"].values():
        assert g["members"] == k
        assert g["steps"] >= 1
        assert g["lanes_last_step"] > 1
    # device backend: requesting the device plan for every tenant's
    # normalized query hits the same cache — ≤ 2 compiles for 128 requests
    from siddhi_tpu.compiler import parse
    from siddhi_tpu.fleet.shape import normalize_query
    from siddhi_tpu.query_api import Query
    for i in range(k):
        app = parse(tenant_apps(body, k)[i])
        defs = dict(app.stream_definitions)
        for el in app.execution_elements:
            if isinstance(el, Query):
                manager.fleet.device_plan(normalize_query(el, defs), defs)
    stats = manager.fleet.stats()
    assert stats["cache"]["per_backend"]["jax"] == 2, stats["cache"]
    assert stats["cache"]["misses"] == 4      # 2 numpy + 2 jax total
    # zero oracle mismatches vs per-app solo execution
    solo_mgr = SiddhiManager()
    try:
        _, solo_rows = run_tenants(
            solo_mgr, tenant_apps(body, k, ann="", name="u"), events,
            out_stream="Out")
        for i in range(k):
            assert_rows_match(solo_rows[i], fleet_rows[i])
    finally:
        solo_mgr.shutdown()


def test_device_plan_executes_with_param_columns(manager):
    """The cached device (jit) program really is tenant-generic: one
    compiled step, two tenants' parameter bindings, both match the scalar
    oracle."""
    import numpy as np
    from siddhi_tpu.compiler import parse
    from siddhi_tpu.fleet.shape import normalize_query
    from siddhi_tpu.query_api import Query

    thresholds = [30.0, 70.0]
    app = parse(STREAM + "from S[v > 30.0] select v, n insert into Out;")
    defs = dict(app.stream_definitions)
    q = [el for el in app.execution_elements if isinstance(el, Query)][0]
    nq = normalize_query(q, defs)
    plan = manager.fleet.device_plan(nq, defs)
    events = gen_events(64, seed=5)
    from siddhi_tpu.tpu.batch import columns_from_rows
    b = columns_from_rows(plan.schema, [r for r, _ in events],
                          [t for _, t in events], capacity=plan.B)
    for thr in thresholds:
        cols = dict(b["cols"])
        for spec, _v in zip(nq.param_specs, nq.param_values):
            cols[f"__fleet_p{spec.index}"] = np.full(
                plan.B, thr, dtype=np.float32)
        state = plan.init_state()
        _st, out = plan._step(state, cols, b["ts"], b["valid"])
        got = int(out["count"])
        want = sum(1 for r, _ in events if r[1] > thr)
        assert got == want


# ---------------------------------------------------------------------------
# isolation, eviction, fallback
# ---------------------------------------------------------------------------

def test_tenant_snapshot_restore_isolation(manager):
    body = (lambda i: f"from S#window.length({5 + i}) select sum(v) as s "
                      f"insert into Out;")
    events = gen_events(120, seed=7)
    runtimes, rows = run_tenants(manager, tenant_apps(body, 3), events,
                                 chunk=11, expect_fleet=3)
    # snapshot tenant 0, feed more data to everyone, restore tenant 0:
    # tenant 0 replays exactly, tenants 1..2 keep their later state
    snap = runtimes[0].snapshot()
    more = gen_events(60, seed=8)
    for rows_t in rows:
        rows_t.clear()
    for row, ts in more:
        for rt in runtimes:
            rt.input_handler("S").send(list(row), timestamp=ts)
    for rt in runtimes:
        rt.flush_host()
    first_pass = [list(r) for r in rows]
    runtimes[0].restore(snap)
    rows[0].clear()
    for row, ts in more:
        runtimes[0].input_handler("S").send(list(row), timestamp=ts)
    runtimes[0].flush_host()
    # tenant 0: identical outputs after restore (exact same window state)
    assert_rows_match(first_pass[0], rows[0])
    # co-tenants were NOT disturbed by tenant 0's restore: feed a bit more
    # and compare against solo runtimes carried through the same history
    solo_mgr = SiddhiManager()
    try:
        srt, srows = run_tenants(
            solo_mgr, tenant_apps(body, 3, ann="", name="u"),
            events + more)
        tail = gen_events(40, seed=9)
        for rows_t in rows:
            rows_t.clear()
        for rows_t in srows:
            rows_t.clear()
        for row, ts in tail:
            for rt in runtimes[1:]:
                rt.input_handler("S").send(list(row), timestamp=ts)
            for rt in srt[1:]:
                rt.input_handler("S").send(list(row), timestamp=ts)
        for rt in runtimes[1:]:
            rt.flush_host()
        for i in (1, 2):
            assert_rows_match(srows[i], rows[i])
    finally:
        solo_mgr.shutdown()


def test_plan_cache_eviction(manager):
    manager.fleet.plan_cache.max_entries = 1
    apps_a = tenant_apps(lambda i: "from S[v > 10.0] select v "
                                   "insert into Out;", 1, name="a")
    rt_a = manager.create_siddhi_app_runtime(apps_a[0], playback=True)
    rt_a.start()
    key_a = rt_a.fleet_bridges[0].group.shape_key
    assert manager.fleet.plan_cache.entry(key_a, "numpy") is not None
    # a second live shape over-admits (both pinned, nothing evictable)
    rt_b = manager.create_siddhi_app_runtime(
        f"@app(name='b0')\n{FLEET}{STREAM}"
        "from S select sum(v) as s insert into Out;", playback=True)
    rt_b.start()
    assert len(manager.fleet.plan_cache) == 2
    assert manager.fleet.plan_cache.evictions == 0
    # tenant a leaves → its entry unpins; the next new shape evicts it
    rt_a.shutdown()
    rt_c = manager.create_siddhi_app_runtime(
        f"@app(name='c0')\n{FLEET}{STREAM}"
        "from S select count() as c insert into Out;", playback=True)
    rt_c.start()
    assert manager.fleet.plan_cache.evictions >= 1
    assert manager.fleet.plan_cache.entry(key_a, "numpy") is None
    # re-arrival of shape A recompiles (miss), runs fine
    misses = manager.fleet.plan_cache.misses
    rt_a2 = manager.create_siddhi_app_runtime(
        apps_a[0].replace("a0", "a1"), playback=True)
    rt_a2.start()
    assert manager.fleet.plan_cache.misses == misses + 1


def test_fallback_mix_does_not_poison_fleet(manager):
    # tenant 1 uses stdDev (no columnar kernel) + an output-rate query (no
    # fleet shape): both keep solo paths while tenants 0/2 stay fleet
    def body(i):
        if i == 1:
            return ("from S select stdDev(v) as sd insert into Out;")
        return f"from S[v > {20.0 + i}] select sym, v insert into Out;"

    events = gen_events(200, seed=11)
    runtimes, fleet_rows = run_tenants(manager, tenant_apps(body, 3),
                                       events, chunk=9)
    assert len(runtimes[0].fleet_bridges) == 1
    assert len(runtimes[1].fleet_bridges) == 0      # solo fallback
    assert len(runtimes[2].fleet_bridges) == 1
    assert manager.fleet.stats()["fallbacks"] >= 1
    solo_mgr = SiddhiManager()
    try:
        _, solo_rows = run_tenants(
            solo_mgr, tenant_apps(body, 3, ann="", name="u"), events)
        for i in range(3):
            assert_rows_match(solo_rows[i], fleet_rows[i])
    finally:
        solo_mgr.shutdown()


def test_non_lowering_shape_negative_cached(manager):
    # a shape that normalizes but has no columnar kernel (lengthBatch):
    # the first tenant pays the one compile attempt, the second hits the
    # negative cache (same shape — only the filter constant differs); both
    # keep the solo path with correct outputs
    body = (lambda i: f"from S[v > {1.0 + i}]#window.lengthBatch(5) "
                      f"select sum(v) as s insert into Out;")
    events = gen_events(80, seed=13)
    runtimes, fleet_rows = run_tenants(manager, tenant_apps(body, 2),
                                       events)
    assert all(not rt.fleet_bridges for rt in runtimes)
    assert manager.fleet.stats()["cache"]["failed"] >= 1
    solo_mgr = SiddhiManager()
    try:
        _, solo_rows = run_tenants(
            solo_mgr, tenant_apps(body, 2, ann="", name="u"), events)
        for i in range(2):
            assert_rows_match(solo_rows[i], fleet_rows[i])
    finally:
        solo_mgr.shutdown()


# ---------------------------------------------------------------------------
# guard: eject → solo → readmit carry-over + poison staging (the full
# containment/chaos matrix lives in tests/test_fleet_guard.py)
# ---------------------------------------------------------------------------

def test_eject_solo_readmit_cycle_preserves_window_state(manager):
    """Snapshot/restore across an eject → solo → readmit cycle: the
    member's window state steps solo through the shared plan, so sums keep
    accumulating across the cycle and snapshots round-trip via
    FleetGroup.member_state/restore_member_state whatever phase the tenant
    is in."""
    import time as _time

    body = (lambda i: f"from S#window.length({6 + i}) select sum(v) as s "
                      f"insert into Out;")
    ann = "@app:fleet(batch='96', guard.cooldown.ms='5', " \
          "guard.readmit.batches='2')\n" \
          "@app:chaos(seed='23', fleet.fault.p='0.5')\n"
    apps = [f"@app(name='t{i}')\n{ann if i == 0 else FLEET}{STREAM}"
            f"{body(i)}" for i in range(3)]
    events = gen_events(300, seed=21)
    runtimes, got = [], []
    for text in apps:
        rt = manager.create_siddhi_app_runtime(text, playback=True)
        rows = []
        rt.add_callback("Out", StreamCallback(
            lambda evs, rows=rows: rows.extend(list(e.data) for e in evs)))
        rt.start()
        runtimes.append(rt)
        got.append(rows)
    for s in range(0, 300, 7):
        if (s // 7) % 2 == 0:
            _time.sleep(0.01)      # let readmission cool-downs elapse
        for rt in runtimes:
            rt.input_handler("S").send_rows(
                [list(r) for r, _ in events[s:s + 7]],
                [t for _, t in events[s:s + 7]])
    for rt in runtimes:
        rt.flush_host()
    lane = runtimes[0].fleet_bridges[0].member.lane
    assert lane.ejections >= 1 and lane.readmissions >= 1
    solo_mgr = SiddhiManager()
    try:
        _, solo = run_tenants(
            solo_mgr, tenant_apps(body, 3, ann="", name="u"), events)
        for i in range(3):
            assert_rows_match(solo[i], got[i])
    finally:
        solo_mgr.shutdown()


def test_mixed_poison_staging_keeps_cotenants_exact(manager):
    """One tenant interleaves NaN and dtype-poisoned rows into its chunks;
    only that tenant's bad rows divert (counted in its lane) and the
    co-tenants' outputs stay complete."""
    apps = tenant_apps(
        lambda i: "from S[v > 5.0] select sym, v, n insert into Out;", 3)
    runtimes, got = [], []
    for text in apps:
        rt = manager.create_siddhi_app_runtime(text, playback=True)
        rows = []
        rt.add_callback("Out", StreamCallback(
            lambda evs, rows=rows: rows.extend(list(e.data) for e in evs)))
        rt.start()
        runtimes.append(rt)
        got.append(rows)
    events = gen_events(120, seed=33)
    for s in range(0, 120, 6):
        for i, rt in enumerate(runtimes):
            chunk = [list(r) for r, _ in events[s:s + 6]]
            if i == 2 and s % 18 == 0:
                chunk[0] = ["sP", float("inf"), 5]
                chunk[1] = ["sQ", None, "not-a-long"]
            rt.input_handler("S").send_rows(
                chunk, [t for _, t in events[s:s + 6]])
    for rt in runtimes:
        rt.flush_host()
    assert runtimes[2].fleet_bridges[0].member.lane.poisoned >= 10
    assert runtimes[0].fleet_bridges[0].member.lane.poisoned == 0
    expected = sum(1 for r, _ in events if r[1] > 5.0)
    assert len(got[0]) == expected and len(got[1]) == expected


# ---------------------------------------------------------------------------
# metrics + teardown
# ---------------------------------------------------------------------------

def test_fleet_metrics_and_unregister_on_shutdown(manager):
    apps = tenant_apps(lambda i: f"@info(name='rule') from S[v > {i + 1.0}] "
                                 f"select v insert into Out;", 2)
    events = gen_events(100, seed=17)
    runtimes, _ = run_tenants(manager, apps, events, chunk=10,
                              expect_fleet=2)
    sm = runtimes[0].ctx.statistics_manager
    gauges = sm.snapshot_trackers()["gauges"]
    assert gauges["fleet.rule.events"].value == 100
    assert gauges["fleet.rule.lanes_per_step"].value >= 1
    assert gauges["fleet.shape_cache.hits"].value >= 1
    assert gauges["fleet.shape_cache.misses"].value == 1
    assert gauges["fleet.rule.ev_per_s"].value > 0
    # tenant 0 shuts down: its member leaves the group, its gauges
    # unregister (no dead gauges reading 0 forever), tenant 1 keeps working
    group = runtimes[0].fleet_bridges[0].group
    runtimes[0].shutdown()
    assert len(group.members) == 1
    assert not any(k.startswith("fleet.")
                   for k in sm.snapshot_trackers()["gauges"])
    more = gen_events(40, seed=18)
    before = group.members[list(group.members)[0]].events_in
    for row, ts in more:
        runtimes[1].input_handler("S").send(list(row), timestamp=ts)
    runtimes[1].flush_host()
    after = group.members[list(group.members)[0]].events_in
    assert after == before + 40
    # last tenant leaves → group dropped, plan stays cached but unpinned
    key = group.shape_key
    runtimes[1].shutdown()
    assert key not in manager.fleet.groups
    assert manager.fleet.plan_cache.entry(key, "numpy").pins == 0


def test_host_bridge_metrics_unregister_on_shutdown(manager):
    rt = manager.create_siddhi_app_runtime(
        "@app:host_batch(batch='64')\n" + STREAM +
        "@info(name='q') from S[v > 1.0] select v insert into Out;",
        playback=True)
    rt.start()
    sm = rt.ctx.statistics_manager
    assert any(k.startswith("host_batch.q")
               for k in sm.snapshot_trackers()["gauges"])
    assert "host_batch.q.step" in sm.snapshot_trackers()["latency"]
    rt.shutdown()
    snap = sm.snapshot_trackers()
    assert not any(k.startswith("host_batch.q")
                   for d in snap.values() for k in d)


# ---------------------------------------------------------------------------
# same-app plan dedupe (host_bridge satellite)
# ---------------------------------------------------------------------------

def test_same_app_duplicate_queries_share_plan(manager):
    rt = manager.create_siddhi_app_runtime(
        "@app:host_batch(batch='64')\n" + STREAM +
        "@info(name='q1') from S[v > 10.0] select sym, v insert into O1;\n"
        "@info(name='q2') from S[v > 10.0] select sym, v insert into O2;\n"
        "@info(name='q3') from S[v > 99.0] select sym, v insert into O3;",
        playback=True)
    rt.start()
    assert len(rt.host_bridges) == 3
    by_name = {b.query_name: b for b in rt.host_bridges}
    # identical shape + identical constants → ONE compiled plan object
    assert by_name["q1"].runtime.compiled is by_name["q2"].runtime.compiled
    assert by_name["q1"].runtime.hq is by_name["q2"].runtime.hq
    # differing constants → distinct plan (no parameter slots in-app)
    assert by_name["q1"].runtime.compiled is not by_name["q3"].runtime.compiled
    # ... and they still execute independently with correct outputs
    got = {o: [] for o in ("O1", "O2", "O3")}
    for o in got:
        rt.add_callback(o, StreamCallback(
            lambda evs, o=o: got[o].extend(list(e.data) for e in evs)))
    for row, ts in gen_events(100, seed=19):
        rt.input_handler("S").send(list(row), timestamp=ts)
    rt.flush_host()
    assert got["O1"] == got["O2"]
    assert len(got["O3"]) <= len(got["O1"])
    assert all(r[1] > 99.0 for r in got["O3"])


def test_same_app_duplicate_patterns_share_plan(manager):
    rt = manager.create_siddhi_app_runtime(
        "@app:host_batch(batch='64')\n" + STREAM +
        "@info(name='p1') from every e1=S[v > 90.0] -> e2=S[v > e1.v] "
        "select e1.v as a, e2.v as b insert into O1;\n"
        "@info(name='p2') from every e1=S[v > 90.0] -> e2=S[v > e1.v] "
        "select e1.v as a, e2.v as b insert into O2;",
        playback=True)
    rt.start()
    by_name = {b.query_name: b for b in rt.host_bridges}
    assert by_name["p1"].runtime.compiler is by_name["p2"].runtime.compiler
    assert by_name["p1"].runtime.engine is by_name["p2"].runtime.engine


# ---------------------------------------------------------------------------
# shape-key lint (scripts/check_fleet_shapes.py)
# ---------------------------------------------------------------------------

def test_fleet_shape_lint_passes():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_fleet_shapes.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr + p.stdout


def test_shape_keys_structure_vs_constants():
    from siddhi_tpu.compiler import parse
    from siddhi_tpu.fleet.shape import normalize_query
    from siddhi_tpu.query_api import Query

    def key_of(body):
        app = parse(STREAM + body)
        q = [e for e in app.execution_elements if isinstance(e, Query)][0]
        return normalize_query(q, dict(app.stream_definitions)).shape_key

    # differing constants (incl. window size, string, within) ⇒ same key
    assert key_of("from S[v > 1.0] select v insert into Out;") == \
        key_of("from S[v > 2.5] select v insert into Out;")
    assert key_of("from S#window.length(5) select sum(v) as s "
                  "insert into Out;") == \
        key_of("from S#window.length(99) select sum(v) as s "
               "insert into Out;")
    assert key_of("from S[sym == 'a'] select v insert into Out;") == \
        key_of("from S[sym == 'b'] select v insert into Out;")
    # differing structure ⇒ different key
    assert key_of("from S[v > 1.0] select v insert into Out;") != \
        key_of("from S[v >= 1.0] select v insert into Out;")
    assert key_of("from S[v > 1.0] select v insert into Out;") != \
        key_of("from S[n > 1] select v insert into Out;")
    assert key_of("from S#window.length(5) select sum(v) as s "
                  "insert into Out;") != \
        key_of("from S#window.time(5 sec) select sum(v) as s "
               "insert into Out;")
    # INT vs DOUBLE constants compile differently ⇒ different key
    assert key_of("from S[n > 5] select v insert into Out;") != \
        key_of("from S[n > 5.5] select v insert into Out;")
