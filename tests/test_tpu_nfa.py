"""Compiled-NFA parity tests: device pattern engine vs the host oracle.

BASELINE.json configs exercised: #2 (A→B sequence-style pattern with within),
#3/#5 shapes (count/Kleene states, partitioned). All on the CPU backend with 8
virtual devices (conftest).
"""

import functools
import random

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu.nfa import DeviceNFARuntime
from siddhi_tpu.tpu.expr_compile import DeviceCompileError
from siddhi_tpu.tpu.partition import PartitionedNFARuntime


def oracle(app, events, out="O"):
    """events: list of (stream_id, row, ts)."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback(out, StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    for sid, row, ts in events:
        rt.input_handler(sid).send(row, timestamp=ts)
    m.shutdown()
    return [e.data for e in got]


def device(app, events, slot_capacity=32, batch_capacity=64):
    rt = DeviceNFARuntime(app, slot_capacity=slot_capacity,
                          batch_capacity=batch_capacity)
    rows = []
    rt.add_callback(rows.extend)
    for sid, row, ts in events:
        rt.send(sid, row, ts)
    rt.flush()
    assert rt.drop_count == 0, "slot overflow would invalidate parity"
    return rows


def assert_match_parity(app, events, **kw):
    from util_parity import assert_rows_match
    assert_rows_match(oracle(app, events), device(app, events, **kw))


APP_2STREAM = """
define stream S1 (sym string, p double);
define stream S2 (sym string, p double);
from every e1=S1[p > 20.0] -> e2=S2[sym == e1.sym and p > e1.p] within 5000
select e1.sym as s, e1.p as p1, e2.p as p2 insert into O;
"""


def gen_2stream(n, seed):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        sid = rng.choice(["S1", "S2"])
        out.append((sid, [rng.choice("abc"), round(rng.uniform(0, 50), 1)],
                    1000 + i * 100))
    return out


def test_parity_two_stream_within():
    assert_match_parity(APP_2STREAM, gen_2stream(120, 11))


def test_parity_every_same_stream():
    app = """
    define stream S (v double);
    from every e1=S[v > 10.0] -> e2=S[v > e1.v]
    select e1.v as a, e2.v as b insert into O;
    """
    rng = random.Random(12)
    events = [("S", [round(rng.uniform(0, 30), 1)], 1000 + i) for i in range(60)]
    assert_match_parity(app, events)


def test_parity_three_state_chain():
    app = """
    define stream S (v double);
    from every e1=S[v > 5.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v]
    select e1.v as a, e2.v as b, e3.v as c insert into O;
    """
    rng = random.Random(13)
    events = [("S", [round(rng.uniform(0, 20), 1)], 1000 + i) for i in range(40)]
    assert_match_parity(app, events, slot_capacity=64)


def test_parity_count_state():
    app = """
    define stream A (v long); define stream B (v long);
    from e1=A<2:4> -> e2=B
    select e1[0].v as f, e1[last].v as l, e2.v as b insert into O;
    """
    events = [("A", [1], 1), ("B", [9], 2), ("A", [2], 3), ("A", [3], 4),
              ("B", [10], 5)]
    assert_match_parity(app, events)


def test_parity_sequence_strict():
    app = """
    define stream A (v long); define stream B (v long);
    from every e1=A, e2=B select e1.v as a, e2.v as b insert into O;
    """
    events = [("A", [1], 1), ("B", [2], 2), ("A", [3], 3), ("A", [4], 4),
              ("B", [5], 5)]
    assert_match_parity(app, events)


def test_eight_state_chain_compiles_and_matches():
    """North-star shape: 8-state rising chain."""
    states = " -> ".join(
        f"e{i}=S[v > e{i-1}.v]" if i > 1 else "e1=S[v > 0.0]"
        for i in range(1, 9))
    sel = ", ".join(f"e{i}.v as v{i}" for i in range(1, 9))
    app = f"""
    define stream S (v double);
    from every {states} within 100000
    select {sel} insert into O;
    """
    # strictly rising input → exactly one full chain per 8 events... every
    # overlapping chain counts; verify vs oracle on a small stream
    rng = random.Random(14)
    events = [("S", [round(rng.uniform(0, 100), 1)], 1000 + i)
              for i in range(30)]
    assert_match_parity(app, events, slot_capacity=128)


def test_partitioned_mesh_parity():
    app = """
    define stream S (dev string, v double);
    from every e1=S[v > 50.0] -> e2=S[dev == e1.dev and v > e1.v]
    select e1.dev as d, e1.v as v1, e2.v as v2 insert into O;
    """
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:8]), ("p",))
    rt = PartitionedNFARuntime(app, num_partitions=8, key_attr="dev",
                               slot_capacity=64, lane_batch=32, mesh=mesh)
    rng = random.Random(15)
    events = []
    for i in range(200):
        events.append(("S", [f"dev{rng.randrange(16)}",
                             round(rng.uniform(0, 100), 1)], 1000 + i))
    for sid, row, ts in events:
        rt.send(sid, row, ts)
    rt.flush()
    assert rt.drop_count == 0
    assert rt.match_count == len(oracle(app, events))


def test_scan_kernel_on_a_mesh_each_shard_takes_its_own_pack():
    """The count-state step sharded over four devices, two lanes a shard:
    one key closes 40 closures on one event, more than its lane's 32 events
    of a batch, so that batch's rows pass the packed table in ONE shard.
    Every shard branches on the largest row count of its own lanes; the
    rows, lane for lane, are those of the same runtime without a mesh."""
    app = """
    define stream S (dev string, v double);
    from every e1=S[v > 50.0] -> e2=S[dev == e1.dev and v > e1.v]<3:>
        -> e3=S[dev == e1.dev and v < e1.v]
    select e1.v as v1, e2[0].v as first, e2[last].v as peak, e3.v as back
    insert into O;
    """
    rng = random.Random(16)
    events = [("S", [f"dev{rng.randrange(12)}",
                     round(rng.uniform(0, 100), 1)], 1000 + i)
              for i in range(160)]
    hot = [("S", ["hot", 52.0 + i / 100], 2000 + i) for i in range(43)]
    events[80:80] = hot + [("S", ["hot", 10.0], 2100)]
    got = {}
    for mesh in (None, Mesh(np.array(jax.devices()[:4]), ("p",))):
        rt = PartitionedNFARuntime(app, num_partitions=8, key_attr="dev",
                                   slot_capacity=64, lane_batch=32,
                                   mesh=mesh)
        assert rt.kernel == "scan" and rt.compiler.M == 32
        rows, most = [], []
        rt.callback = rows.extend
        inner = rt.decode_stacked
        rt.decode_stacked = lambda ys, inner=inner, most=most: (
            most.append(np.asarray(ys["n"])), inner(ys))[1]
        for sid, row, ts in events:
            rt.send(sid, list(row), ts)
        rt.flush()
        assert rt.drop_count == 0 and len(rows) >= 40
        over = [n for n in most if n.max() > 32]
        assert len(over) == 1 and int((over[0] > 32).sum()) == 1
        got[mesh is None] = rows
    assert got[True] == got[False]


def test_partitioned_per_key_semantics_on_shared_lanes():
    """`partition with` means per-KEY pattern instances. With more keys than
    lanes, a lane sees several keys interleaved — the implicit
    `key == e1.key` constraint must stop chains stitching across keys
    (without it the device emitted cross-key matches the host never
    produced)."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    app = """
    define stream S (dev string, v double);
    partition with (dev of S)
    begin
    from every e1=S[v > 90.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v]
    select e1.v as v1, e2.v as v2, e3.v as v3 insert into Alerts;
    end;
    """
    # ONE lane, two keys: interleaved rising values must only match per key
    rt = PartitionedNFARuntime(app, num_partitions=1, key_attr="dev",
                               slot_capacity=16, lane_batch=64)
    seq = [("a", 91.0), ("b", 92.0), ("a", 93.0), ("b", 94.0),
           ("a", 95.0), ("b", 96.0)]
    ts = 1000
    for d, v in seq:
        rt.send("S", [d, v], ts)
        ts += 10
    rt.flush()

    m = SiddhiManager()
    hrt = m.create_siddhi_app_runtime(app, playback=True)
    hm = []
    hrt.add_callback("Alerts", StreamCallback(
        lambda evs: hm.extend(list(e.data) for e in evs)))
    hrt.start()
    ts = 1000
    for d, v in seq:
        hrt.input_handler("S").send([d, v], timestamp=ts)
        ts += 10
    m.shutdown()
    assert rt.match_count == len(hm) == 2
    # sequences can't take the shared-lane path (per-key strictness)
    with pytest.raises(DeviceCompileError):
        PartitionedNFARuntime("""
        define stream S (dev string, v double);
        partition with (dev of S)
        begin
        from every e1=S[v > 0], e2=S[v > e1.v]
        select e1.v as v1, e2.v as v2 insert into Alerts;
        end;
        """, num_partitions=2, key_attr="dev")


def test_unsupported_patterns_fall_back():
    # absent without `for` (followed-by semantics) stays on host
    with pytest.raises(DeviceCompileError):
        DeviceNFARuntime("""
        define stream A (v long); define stream B (v long); define stream C (v long);
        from e1=A -> not B -> e3=C select e3.v as v insert into O;
        """)
    # sibling alias reference inside a logical state (unbound-side semantics)
    with pytest.raises(DeviceCompileError):
        DeviceNFARuntime("""
        define stream A (v long); define stream B (v long); define stream C (v long);
        from e1=A -> e2=B and e3=C[v > e2.v] select e1.v as v insert into O;
        """)
    # absent states inside sequences (strict continuity × non-occurrence)
    with pytest.raises(DeviceCompileError):
        DeviceNFARuntime("""
        define stream A (v long); define stream B (v long); define stream C (v long);
        from every e1=A, not B for 1 sec, e3=C select e1.v as v insert into O;
        """)
    # non-null-strict predicate over a possibly-unbound binding (e1[2] may
    # be NULL; `or` is not null-strict, so host null semantics apply)
    with pytest.raises(DeviceCompileError):
        DeviceNFARuntime("""
        define stream A (v long); define stream B (v long);
        from e1=A<0:5> -> e2=B[v > e1[0].v or v < 0]
        select e2.v as v insert into O;
        """)
    # back-to-back counts: no device advance edge between count tables
    with pytest.raises(DeviceCompileError):
        DeviceNFARuntime("""
        define stream A (v long); define stream B (v long);
        from e1=A<1:2> -> e2=B<1:3>
        select e1[0].v as a, e2[0].v as b insert into O;
        """)


def test_count_variant_keys_tolerate_marker_like_attribute_names():
    """Attributes named 'occupancy'/'last_x' must not collide with the
    count-variant key markers (keys use '#', illegal in identifiers)."""
    rt = DeviceNFARuntime("""
    define stream A (occupancy long);
    define stream B (v long);
    from e1=A[occupancy>0]<2:5> -> e2=B[v>e1[1].occupancy]
    select e1[0].occupancy as o0, e1[1].occupancy as o1, e2.v as v
    insert into O;
    """, slot_capacity=8, batch_capacity=8)
    rows = []
    rt.add_callback(rows.extend)
    for i, (sid, row) in enumerate([("A", [3]), ("A", [4]), ("B", [9])]):
        rt.send(sid, row, 1000 + i * 100)
    rt.flush()
    assert rows == [[3, 4, 9]]
    # attribute ENDING in 'flag' referenced only via e[k]: must not be
    # misclassified as a synthetic occurrence flag (used_cols skip)
    rt = DeviceNFARuntime("""
    define stream A (myflag long);
    define stream B (v long);
    from e1=A[myflag>0]<2:5> -> e2=B[v>0]
    select e1[1].myflag as o1, e2.v as v insert into O;
    """, slot_capacity=8, batch_capacity=8)
    rows = []
    rt.add_callback(rows.extend)
    for i, (sid, row) in enumerate([("A", [3]), ("A", [4]), ("B", [9])]):
        rt.send(sid, row, 1000 + i * 100)
    rt.flush()
    assert rows == [[4, 9]]


# ---------------------------------------------------------------- logical/absent

APP_AND_CHAIN = """
define stream A (v long);
define stream B (v long);
define stream C (v long);
from every e1=A[v > 0] -> e2=B[v > 10] and e3=C[v > 20]
select e1.v as a, e2.v as b, e3.v as c insert into O;
"""


def test_parity_logical_and_mid_chain():
    evs = [("A", [1], 1000), ("B", [11], 1001), ("C", [21], 1002),
           ("A", [2], 1003), ("C", [25], 1004), ("B", [15], 1005),
           ("B", [5], 1006), ("C", [30], 1007)]
    assert_match_parity(APP_AND_CHAIN, evs)


def test_parity_logical_and_randomized():
    rng = random.Random(21)
    evs = []
    for i in range(300):
        sid = rng.choice(["A", "B", "C"])
        evs.append((sid, [rng.randrange(40)], 1000 + i))
    assert_match_parity(APP_AND_CHAIN, evs, slot_capacity=64)


def test_parity_logical_or_randomized():
    app = """
    define stream A (v long);
    define stream B (v long);
    define stream C (v long);
    from every e1=A[v > 5] -> e2=B[v > 10] or e3=C[v > 20]
    select e1.v as a insert into O;
    """
    rng = random.Random(22)
    evs = [(rng.choice(["A", "B", "C"]), [rng.randrange(40)], 1000 + i)
           for i in range(300)]
    assert_match_parity(app, evs, slot_capacity=64)


def test_parity_logical_first_state():
    # logical at state 0 (AND + OR), seeds consumed correctly without `every`
    app_and = """
    define stream A (v long);
    define stream B (v long);
    define stream C (v long);
    from e1=A[v > 0] and e2=B[v > 0] -> e3=C[v > 0]
    select e1.v as a, e2.v as b, e3.v as c insert into O;
    """
    evs = [("B", [7], 1), ("A", [3], 2), ("C", [9], 3), ("C", [4], 4)]
    assert_match_parity(app_and, evs)
    app_or = """
    define stream A (v long);
    define stream B (v long);
    define stream C (v long);
    from every e1=A[v > 0] or e2=B[v > 0] -> e3=C[v > 0]
    select e3.v as c insert into O;
    """
    evs2 = [("B", [7], 1), ("C", [9], 2), ("A", [3], 3), ("C", [4], 4)]
    assert_match_parity(app_or, evs2)


def test_parity_and_not():
    app = """
    define stream A (v long);
    define stream B (v long);
    define stream C (v long);
    from every e1=A[v > 0] -> e2=B[v > 10] and not C
    select e1.v as a, e2.v as b insert into O;
    """
    evs = [("A", [1], 1), ("C", [0], 2), ("B", [11], 3),
           ("A", [2], 4), ("B", [12], 5)]
    assert_match_parity(app, evs)


APP_ABSENT_CHAIN = """
define stream A (v long);
define stream B (v long);
define stream C (v long);
from every e1=A[v > 0] -> not B for 100 -> e3=C[v > 0]
select e1.v as a, e3.v as c insert into O;
"""


def test_parity_absent_mid_chain():
    evs = [("A", [1], 1000), ("B", [9], 1050), ("C", [7], 1200),   # killed
           ("A", [2], 2000), ("C", [8], 2150),                     # matches
           ("A", [3], 3000), ("C", [9], 3050)]                     # too early
    assert_match_parity(APP_ABSENT_CHAIN, evs)


def test_parity_absent_randomized():
    rng = random.Random(23)
    evs, ts = [], 1000
    for _ in range(250):
        ts += rng.choice([10, 30, 60, 150])
        evs.append((rng.choice(["A", "B", "C"]), [rng.randrange(20)], ts))
    assert_match_parity(APP_ABSENT_CHAIN, evs, slot_capacity=64)


def test_parity_chained_absents():
    """Review regression: back-to-back absents chain their timers — the second
    wait starts at the first's expiry, not at the next event arrival."""
    app = """
    define stream A (v long);
    define stream B (v long);
    define stream C (v long);
    define stream D (v long);
    from every e1=A[v > 0] -> not B for 100 -> not C for 50 -> e4=D[v > 0]
    select e1.v as a, e4.v as d insert into O;
    """
    evs = [("A", [1], 1000), ("D", [5], 1300),    # both waits long since done
           ("A", [2], 2000), ("C", [3], 2120),    # C inside second window
           ("D", [6], 2300)]
    assert_match_parity(app, evs)


def test_parity_every_and_first_state():
    """Review regression: `every (A and B)` keeps ONE half-bound seed that
    rebinds sides — it must not spawn a seed per matching event."""
    app = """
    define stream A (v long);
    define stream B (v long);
    define stream C (v long);
    from every (e1=A[v > 0] and e2=B[v > 0]) -> e3=C[v > 0]
    select e1.v as a, e2.v as b, e3.v as c insert into O;
    """
    evs = [("A", [1], 1), ("A", [2], 2), ("B", [3], 3), ("C", [4], 4),
           ("B", [5], 5), ("A", [6], 6), ("C", [7], 7)]
    assert_match_parity(app, evs)


def test_parity_absent_final():
    # `A -> not B for t` at the end: emission on the next event past the wait
    app = """
    define stream A (v long);
    define stream B (v long);
    from every e1=A[v > 0] -> not B for 100
    select e1.v as a insert into O;
    """
    evs = [("A", [1], 1000), ("A", [2], 1200),    # A@1000 established by 1200
           ("B", [9], 1250),                       # kills A@1200's waiter
           ("A", [3], 1400)]                       # nothing pending besides new
    exp = oracle(app, evs)
    act = device(app, evs)
    assert sorted(map(tuple, exp)) == sorted(map(tuple, act))


def test_absent_for_arms_at_timestamp_zero():
    """A partial whose predecessor matched at ts=0 must still expire its
    `not X for t` wait (arrive_ts==0 is a real arm time, not 'unset')."""
    app = """
    define stream A (v long); define stream B (v long); define stream C (v long);
    from e1=A -> not B for 100 -> e3=C
    select e1.v as a, e3.v as c insert into O;
    """
    evs = [("A", [7], 0),          # arms the non-occurrence clock at ts=0
           ("C", [9], 200)]        # after expiry: must match (7, 9)
    assert_match_parity(app, evs)


def test_within_expires_partial_seeded_at_timestamp_zero():
    """`within` must expire a partial whose chain started at ts=0
    (first_ts==0 is a real bind time, not 'unset')."""
    app = """
    define stream A (v long); define stream B (v long);
    from e1=A -> e2=B within 100
    select e1.v as a, e2.v as b insert into O;
    """
    evs = [("A", [7], 0), ("B", [9], 500)]      # expired: no match
    assert_match_parity(app, evs)
    evs2 = [("A", [7], 0), ("B", [9], 50)]      # inside window: match
    assert_match_parity(app, evs2)


# ---------------------------------------------------------------------------
# the scan kernel's rows, packed on the device (ISSUE 33): the row table
# against the boolean index over the per-event emit grids it replaced
# ---------------------------------------------------------------------------

APP_COUNT_KLEENE = """
define stream S (k string, v double);
from every e1=S[v > 10.0] -> e2=S[k == e1.k and v > e1.v]<2:> -> e3=S[k == e1.k and v < e1.v] within 40
select e1.v as a, e2[0].v as f, e2[1].v as g, e2[last].v as l, e3.v as z
insert into O;
"""
APP_ZERO_MIN = """
define stream A (v long); define stream B (v long);
from every e1=A[v > 3] -> e2=B[v > e1.v]<0:2> -> e3=A[v < e1.v]
select e1.v as a, e2[0].v as f, e2[last].v as l, e3.v as z insert into O;
"""
APP_OR_NULLS = """
define stream A (v long);
define stream B (v long);
define stream C (v long);
from every e1=A[v > 5] -> e2=B[v > 10] or e3=C[v > 20]
select e1.v as a, e2.v as b, e3.v as c insert into O;
"""


def _abc_events(seed, n=300):
    rng = random.Random(seed)
    return [(rng.choice(["A", "B", "C"]), [rng.randrange(40)], 1000 + i * 7)
            for i in range(n)]


def _ab_events(seed, n=300):
    rng = random.Random(seed)
    return [(rng.choice(["A", "B"]), [rng.randrange(12)], 1000 + i)
            for i in range(n)]


def _kleene_events(seed, n=400):
    rng = random.Random(seed)
    return [("S", [rng.choice("xyz"), round(rng.uniform(0, 40), 1)],
             1000 + i) for i in range(n)]


def _absent_events(seed, n=250):
    rng = random.Random(seed)
    evs, ts = [], 1000
    for _ in range(n):
        ts += rng.choice([10, 30, 60, 150])
        evs.append((rng.choice(["A", "B", "C"]), [rng.randrange(20)], ts))
    return evs


SCAN_CORPUS = {
    "count-kleene": (APP_COUNT_KLEENE, _kleene_events(31)),
    "count-zero-min-nulls": (APP_ZERO_MIN, _ab_events(32)),
    "logical-and": (APP_AND_CHAIN, _abc_events(21)),
    "logical-or-nulls": (APP_OR_NULLS, _abc_events(22)),
    "absent": (APP_ABSENT_CHAIN, _absent_events(23)),
}


def _walk_batches(rt, events):
    """``events`` through ``rt``'s builder: the step's arguments, a batch at
    a time."""
    for sid, row, ts in events:
        rt.builder.append(sid, row, ts)
        if rt.builder.full or (sid, row, ts) == events[-1]:
            b = rt.builder.emit()
            yield (b["cols"], b["tag"], b["ts"], b["ts_base"],
                   np.int32(b["count"]))


def _assert_table_is_the_index(nfa, table, grids, upto=None):
    """``table``'s rows are the boolean index over the emit grids (its
    first ``upto``): rows, order, ``j`` and NULL masks. Returns how many NULL
    cells were compared."""
    mask = np.asarray(grids["mask"])                        # [B, R, C]
    got = nfa.decode_outputs(table)
    kept = int(mask.sum()) if upto is None else upto
    assert len(got) == kept
    n_null = 0
    for (col, _, _) in nfa.out_specs:
        assert np.array_equal(np.asarray(grids[col])[mask][:kept],
                              got.cols[col] if kept else []), col
        if f"null__{col}" in grids and kept:
            want = np.asarray(grids[f"null__{col}"])[mask][:kept]
            assert np.array_equal(want, got.nulls[col]), col
            n_null += int(want.sum())
    j = np.asarray(table["j"])[np.asarray(table["mask"])]
    assert np.array_equal(j, np.nonzero(mask)[0][:kept])    # the events
    return n_null


@pytest.mark.parametrize("batch", [48, 4], ids=["packed", "past-the-batch"])
@pytest.mark.parametrize("name", list(SCAN_CORPUS))
def test_the_row_table_holds_the_rows_the_boolean_index_gave(name, batch):
    """Same rows, same order (match event, source, candidate), NULL masks
    kept, batch after batch from the same carried state: in ``full``
    always, and in the packed ``[B]`` table where the batch emitted at most
    ``B`` rows (else it holds the first ``B``). With batches of 4 events
    some batch of every plan emits more
    rows than it has events. The state the step carries is the state the
    scan alone leaves, bit for bit."""
    app, events = SCAN_CORPUS[name]
    rt = DeviceNFARuntime(app, slot_capacity=32, batch_capacity=batch)
    nfa = rt.compiler
    assert not nfa.blocked and nfa.M == nfa.B == batch
    rows_full = nfa._row_capacity()
    assert batch < rows_full <= 2 * 32 * batch
    scan = jax.jit(nfa.make_scan())
    state_g = nfa.init_state()
    n_rows = n_null = n_over = 0
    for args in _walk_batches(rt, events):
        state_g, grids = scan(state_g, *args)
        assert grids["mask"].shape[0::2] == (batch, 32)
        rt.state, ys = nfa._step(rt.state, *args)
        n, full = int(ys.pop("n")), ys.pop("full")
        assert set(ys) == set(full) == {"mask", "j"} | (set(grids) - {"mask"})
        assert all(v.shape == (batch,) for v in ys.values())
        assert all(v.shape == (rows_full,) for v in full.values())
        assert n == int(np.asarray(grids["mask"]).sum()) <= rows_full
        n_null += _assert_table_is_the_index(nfa, full, grids)
        _assert_table_is_the_index(nfa, ys, grids, upto=min(n, batch))
        n_over += n > batch
        n_rows += n
        # the carried state is the same state
        for a, b_ in zip(jax.tree_util.tree_leaves(state_g),
                         jax.tree_util.tree_leaves(rt.state)):
            assert np.array_equal(np.asarray(a), np.asarray(b_))
    assert n_rows > 10
    assert (n_over > 0) == (batch == 4)
    assert (n_null > 0) == name.endswith("nulls")
    assert rt.drop_count == 0


@pytest.mark.parametrize("lanes, cands", [(3, 40), (8, 40), (16, 300)],
                         ids=["plain", "one-tile", "tiles-and-groups"])
def test_rows_past_the_table_are_counted_into_drops(lanes, cands):
    """A table too small for what a batch emits holds the first rows in
    order and ``n`` counts them all (``pack_rows``), whether the lanes are
    numbered plainly or by tiles of 8 and whether a source is one group of
    cells or three; the step counts the rows past its ``full`` table into
    ``drops`` (the next test), never silent."""
    from siddhi_tpu.tpu.nfa import pack_rows
    rng = np.random.default_rng(5)
    mask = rng.uniform(size=(16, lanes, 2, cands)) < 0.2
    vals = rng.uniform(size=(16, lanes, 2, cands)).astype(np.float32)
    most = int(mask.sum(axis=(0, 2, 3)).max())

    @functools.partial(jax.jit, static_argnums=2)
    def pack(mask, vals, n_rows):
        n, table = pack_rows(mask, {"x": vals})
        return n, table(n_rows)

    for n_rows in (8, most // 2, most + 9):
        n, out = jax.tree_util.tree_map(np.asarray, pack(mask, vals, n_rows))
        for lane in range(lanes):
            want = vals[:, lane][mask[:, lane]]
            kept = min(n_rows, len(want))
            assert int(n[lane]) == len(want)
            assert int(out["mask"][lane].sum()) == kept
            assert np.array_equal(out["x"][lane, :kept], want[:kept])
            assert np.array_equal(out["j"][lane, :kept],
                                  np.nonzero(mask[:, lane])[0][:kept])
            assert not out["x"][lane, kept:].any()


def test_rows_past_the_full_table_are_counted_into_the_steps_drops(
        monkeypatch):
    """The step with a ``full`` table smaller than what a batch emits:
    ``full`` holds the first rows in order, the packed table its first
    ``B``, ``n`` counts them all and ``drops`` the rows past ``full``."""
    from siddhi_tpu.tpu.nfa import DeviceNFACompiler
    monkeypatch.setattr(DeviceNFACompiler, "_row_capacity", lambda self: 6)
    app, events = SCAN_CORPUS["count-kleene"]
    rt = DeviceNFARuntime(app, slot_capacity=32, batch_capacity=4)
    nfa = rt.compiler
    scan = jax.jit(nfa.make_scan())
    state_g = nfa.init_state()
    lost = 0
    for args in _walk_batches(rt, events):
        state_g, grids = scan(state_g, *args)
        rt.state, ys = nfa._step(rt.state, *args)
        n, full = int(ys.pop("n")), ys.pop("full")
        assert n == int(np.asarray(grids["mask"]).sum())
        assert full["mask"].shape == (6,) and ys["mask"].shape == (4,)
        _assert_table_is_the_index(nfa, full, grids, upto=min(n, 6))
        _assert_table_is_the_index(nfa, ys, grids, upto=min(n, 4))
        lost += max(n - 6, 0)
        assert rt.drop_count == lost
    assert lost > 0


def test_the_stacked_step_packs_under_one_scalar_branch():
    """The optimized HLO of the lane-stacked scan step holds ONE
    ``conditional``, its predicate one scalar for all lanes, and a pack
    (its row gathers) in either branch: the packed table's and the whole
    one's. A branch a lane (``vmap`` of the lane's step) lowers to a select
    with both packs on every batch, and holds none."""
    import re
    rt = PartitionedNFARuntime(
        SCAN_CORPUS["count-kleene"][0], num_partitions=6, key_attr="k",
        slot_capacity=16, lane_batch=32)
    nfa = rt.compiler
    assert rt.kernel == "scan"
    b = rt.builders[0].emit()
    feed = jax.tree_util.tree_map(
        lambda x: np.stack([x] * 6),
        (b["cols"], b["tag"], b["ts"], b["ts_base"], np.int32(0)))

    def hlo(step):
        return jax.jit(step).lower(rt.init_state(), *feed).compile().as_text()

    def gathers(text, where):
        return [shape for shape, scope in re.findall(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) gather\(.*?op_name="
            r"\"([^\"]*)\"", text, re.M) if where in scope]

    text = hlo(nfa.make_step(stacked=True))
    conds = re.findall(r"^.* conditional\(%?([\w.\-]+),.*$", text, re.M)
    assert len(conds) == 1
    pred = re.search(rf"^\s*%?{re.escape(conds[0])} = (\S+) ", text, re.M)
    assert pred.group(1) in ("pred[]", "s32[]")
    n_cols = len(nfa.out_specs) + 1         # e2[1] may be NULL: its mask
    for branch, n_rows in (("branch_1_fun", nfa.B),
                           ("branch_0_fun", nfa._row_capacity())):
        got = gathers(text, f"nfa.compact/cond/{branch}/")
        # the running group counts, the cells, a gather a column
        assert len(got) == 2 + n_cols, got
        assert all(g.split("[")[1].startswith(f"{6 * n_rows},")
                   for g in got), got
    assert not gathers(text.replace("nfa.compact/cond/", ""), "nfa.compact")
    alone = hlo(jax.vmap(nfa.make_step()))
    assert " conditional(" not in alone
    assert len(gathers(alone, "nfa.compact")) == 2 * (2 + n_cols)


@pytest.mark.parametrize("app, live, per_event", [
    (APP_COUNT_KLEENE, 1, 1),       # only the count state's table fills
    (APP_AND_CHAIN, 1, 1),          # p1 holds, the logical state waits there
    (APP_ABSENT_CHAIN, 2, 1),       # p1 (absent, clocked) and p2
    ("define stream A (v long); define stream B (v long);\n"
     "from not A for 100 -> e2=B select e2.v as b insert into O;", 2, 2),
], ids=["count", "logical", "absent", "absent-start"])
def test_the_row_tables_size_is_read_from_the_plan(app, live, per_event):
    """The packed table has a row an event of the batch, the blocked
    kernel's rule; ``full`` beside it has the plan's bound."""
    rt = DeviceNFARuntime(app, slot_capacity=32, batch_capacity=48)
    nfa = rt.compiler
    assert nfa.M == nfa.B == 48 and rt.fence_key == "n"
    assert nfa._row_capacity() == live * 32 + per_event * 48
    b = rt.builder.emit()                   # a batch of no events
    _, ys = nfa._step(rt.state, b["cols"], b["tag"], b["ts"], b["ts_base"],
                      np.int32(b["count"]))
    assert ys["n"].shape == () and ys["mask"].shape == (48,)
    assert ys["full"]["mask"].shape == (nfa._row_capacity(),)


def test_the_stacked_scan_decode_equals_the_per_lane_decode():
    """As ``test_device_partition`` has it for the blocked kernel: on seeded
    lane-stacked row tables of a count-state plan (NULL masks included),
    the one-pass decode gives the rows a loop over lanes gives."""
    app = """
    define stream S (dev string, v double);
    from every e1=S[v > 50.0] -> e2=S[v > e1.v]<2:5> -> e3=S[v < e1.v]
    select e1.v as v1, e2[3].v as fourth, e3.v as back insert into Alerts;
    """
    rt = PartitionedNFARuntime(app, num_partitions=6, key_attr="dev",
                               slot_capacity=16, lane_batch=32)
    nfa = rt.compiler
    assert rt.kernel == "scan" and nfa.M == 32
    rng = np.random.default_rng(31)
    for density in (0.0, 0.05, 0.4, 1.0):
        mask = rng.uniform(size=(6, nfa.M)) < density
        mask[4] = False
        ys = {"mask": mask,
              "j": np.sort(rng.integers(0, 32, (6, nfa.M)), axis=1).astype(
                  np.int32),
              "null__fourth": rng.uniform(size=(6, nfa.M)) < 0.5}
        for name, _, _ in nfa.out_specs:
            ys[name] = rng.uniform(0, 100, (6, nfa.M)).astype(np.float32)
        old = []
        for lane in range(6):
            old.extend(nfa.decode_outputs(
                jax.tree_util.tree_map(lambda x: x[lane], ys)).rows())
        got = rt.decode_stacked(ys)
        assert got.rows() == old and len(got) == int(mask.sum())
        if density == 1.0:
            assert any(r[1] is None for r in old)
