"""Blocked NFA kernel (nfa_block.py): parity vs the host oracle AND vs the
per-event scan kernel, kernel-selection logic, capacity semantics."""

import functools
import random

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu.nfa import DeviceNFACompiler, DeviceNFARuntime
from util_parity import assert_rows_match, assert_same_chunk


def oracle(app, events, out="O"):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback(out, StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    for sid, row, ts in events:
        rt.input_handler(sid).send(row, timestamp=ts)
    m.shutdown()
    return [e.data for e in got]


def device(app, events, slot_capacity=32, batch_capacity=64,
           force_scan=False, monkeypatch=None):
    if force_scan:
        import siddhi_tpu.tpu.nfa_block as nb
        with monkeypatch.context() as mp:
            mp.setattr(nb, "blocked_eligible", lambda c: False)
            rt = DeviceNFARuntime(app, slot_capacity=slot_capacity,
                                  batch_capacity=batch_capacity)
    else:
        rt = DeviceNFARuntime(app, slot_capacity=slot_capacity,
                              batch_capacity=batch_capacity)
    assert rt.compiler.blocked == (not force_scan)
    rows = []
    rt.add_callback(rows.extend)
    for sid, row, ts in events:
        rt.send(sid, row, ts)
    rt.flush()
    return rows, rt


CHAIN3 = """
define stream S (sym string, v double);
from every e1=S[v > 20.0] -> e2=S[sym == e1.sym and v > e1.v]
  -> e3=S[v > e2.v] within 6000
select e1.sym as s, e1.v as a, e2.v as b, e3.v as c insert into O;
"""

SEQ2 = """
define stream S (v double);
from every e1=S[v > 10.0], e2=S[v > e1.v]
select e1.v as a, e2.v as b insert into O;
"""

TWO_STREAM = """
define stream S1 (sym string, p double);
define stream S2 (sym string, p double);
from every e1=S1[p > 20.0] -> e2=S2[sym == e1.sym and p > e1.p] within 5000
select e1.sym as s, e1.p as p1, e2.p as p2 insert into O;
"""


def gen_one_stream(n, seed, hi=50):
    rng = random.Random(seed)
    return [("S", [rng.choice("ab"), round(rng.uniform(0, hi), 1)],
             1000 + i * 50) for i in range(n)]


def gen_two_stream(n, seed):
    rng = random.Random(seed)
    return [(rng.choice(["S1", "S2"]),
             [rng.choice("abc"), round(rng.uniform(0, 50), 1)],
             1000 + i * 100) for i in range(n)]


def test_kernel_selection():
    defs = """
    define stream S (v double);
    """
    blocked = DeviceNFARuntime(defs + """
    from every e1=S[v > 1.0] -> e2=S[v > e1.v]
    select e1.v as a, e2.v as b insert into O;
    """)
    assert blocked.compiler.blocked
    scan = DeviceNFARuntime(defs + """
    from every e1=S[v > 1.0] -> e2=S[v > e1.v]<2:4> -> e3=S[v > 40.0]
    select e1.v as a, e3.v as c insert into O;
    """)
    assert not scan.compiler.blocked        # count state → per-event kernel


def test_blocked_parity_chain3_vs_oracle():
    events = gen_one_stream(150, 21)
    rows, rt = device(CHAIN3, events)
    assert rt.drop_count == 0
    assert_rows_match(oracle(CHAIN3, events), rows)


def test_blocked_parity_two_stream_vs_oracle():
    events = gen_two_stream(150, 22)
    rows, rt = device(TWO_STREAM, events)
    assert rt.drop_count == 0
    assert_rows_match(oracle(TWO_STREAM, events), rows)


def test_blocked_parity_sequence_vs_oracle():
    rng = random.Random(23)
    events = [("S", [round(rng.uniform(0, 30), 1)], 1000 + i * 50)
              for i in range(120)]
    rows, rt = device(SEQ2, events)
    assert rt.drop_count == 0
    assert_rows_match(oracle(SEQ2, events), rows)


def test_blocked_vs_scan_kernel(monkeypatch):
    """The two kernels agree exactly when no capacity pressure exists."""
    for seed in (31, 32, 33):
        events = gen_one_stream(100, seed)
        b_rows, b_rt = device(CHAIN3, events)
        s_rows, s_rt = device(CHAIN3, events, force_scan=True,
                              monkeypatch=monkeypatch)
        assert b_rt.drop_count == 0 and s_rt.drop_count == 0
        assert_rows_match(s_rows, b_rows)


def test_blocked_small_batches_parity():
    """Partials must advance correctly ACROSS micro-batch boundaries."""
    events = gen_one_stream(90, 41)
    rows, rt = device(CHAIN3, events, batch_capacity=8)
    assert rt.drop_count == 0
    assert_rows_match(oracle(CHAIN3, events), rows)


def test_blocked_within_expiry_across_batches():
    app = """
    define stream S (v double);
    from every e1=S[v > 20.0] -> e2=S[v > e1.v] within 100
    select e1.v as a, e2.v as b insert into O;
    """
    events = [("S", [25.0], 1000),
              ("S", [30.0], 1050),     # within: match (25,30)
              ("S", [40.0], 2000),     # both too old; 30-seed expired too
              ("S", [50.0], 2050)]     # match (40,50)
    rows, rt = device(app, events, batch_capacity=2)
    assert_rows_match(oracle(app, events), rows)


def test_blocked_capacity_truncation_counts_drops():
    """More than C surviving partials at a batch boundary → drop-newest,
    counted (batch-boundary capacity semantics; nfa_block.py docstring)."""
    app = """
    define stream S (v double);
    from every e1=S[v > 0.0] -> e2=S[v > 1000.0]
    select e1.v as a, e2.v as b insert into O;
    """
    # 64 seeds survive every batch; capacity 8 → drops
    events = [("S", [float(i + 1)], 1000 + i) for i in range(64)]
    rows, rt = device(app, events, slot_capacity=8, batch_capacity=16)
    assert rows == []
    assert rt.drop_count > 0
    # the 8 NEWEST seeds survive (drop-newest keeps oldest-created; with all
    # seeds equivalent the kept set is the first-created 8)
    trigger = [("S", [2000.0], 1100)]
    rt.send("S", trigger[0][1], trigger[0][2])
    rt.flush()


def test_and_single_event_binds_both_sides():
    """One event satisfying both AND branches completes the logical state on
    the spot — host and device agree (reference LogicalPatternTestCase
    testQuery5 shape, single-stream variant)."""
    app = """
    define stream A (v double);
    define stream B (v double);
    from e1=A[v > 1.0] -> e2=B[v > 10.0] and e3=B[v < 100.0]
    select e1.v as a, e2.v as b, e3.v as c insert into O;
    """
    events = [("A", [5.0], 1000), ("B", [50.0], 1100)]
    host = oracle(app, events)
    rt = DeviceNFARuntime(app, slot_capacity=16, batch_capacity=16)
    assert not rt.compiler.blocked       # logical state → scan kernel
    rows = []
    rt.add_callback(rows.extend)
    for sid, row, ts in events:
        rt.send(sid, row, ts)
    rt.flush()
    assert host == [[5.0, 50.0, 50.0]]
    assert_rows_match(host, rows)


def test_blocked_snapshot_roundtrip():
    events = gen_one_stream(40, 51)
    rows1, rt = device(CHAIN3, events)
    snap = rt.snapshot_state()
    rt2 = DeviceNFARuntime(CHAIN3, slot_capacity=32, batch_capacity=64)
    rt2.restore_state(snap)
    more = gen_one_stream(40, 52)
    out1, out2 = [], []
    rt.add_callback(out1.extend)
    rt2.add_callback(out2.extend)
    for sid, row, ts in more:
        ts += 3000
        rt.send(sid, row, ts)
        rt2.send(sid, row, ts)
    rt.flush()
    rt2.flush()
    assert_rows_match(out1, out2)


def test_element_within_on_device():
    """Element-level `within` (gap between consecutive elements) runs on the
    blocked kernel; the scan kernel still rejects it."""
    app = """
    define stream S (v double);
    from every e1=S[v > 10.0] -> e2=S[v > e1.v] within 1 sec
      -> e3=S[v > e2.v]
    select e1.v as a, e2.v as b, e3.v as c insert into O;
    """
    # e2 must arrive within 1s of e1's bind; e3 is unconstrained
    events = [("S", [11.0], 1000), ("S", [12.0], 1500),   # gap 500: ok
              ("S", [20.0], 9000),                         # e3 for chain 1;
                                                           # also seeds
              ("S", [30.0], 11000),                        # >1s after 20.0:
                                                           # can't be ITS e2
              ("S", [31.0], 11200)]                        # e2 for 30-seed
    host = oracle(app, events)
    rt = DeviceNFARuntime(app, slot_capacity=16, batch_capacity=4)
    assert rt.compiler.blocked
    rows = []
    rt.add_callback(rows.extend)
    for sid, row, ts in events:
        rt.send(sid, row, ts)
    rt.flush()
    assert_rows_match(host, rows)
    assert [11.0, 12.0, 20.0] in [list(r) for r in rows]
    # the 20-seed's e2 window expired before 30.0 arrived
    assert not any(r[:2] == [20.0, 30.0] for r in rows)

    # dead partials whose element window lapsed must be pruned, not wedge
    # the keep-oldest slots (review finding): C=4, 8 seeds expire unmatched,
    # then a fresh seed must still match
    rt2 = DeviceNFARuntime(app, slot_capacity=4, batch_capacity=4)
    rows2 = []
    rt2.add_callback(rows2.extend)
    for i in range(8):
        rt2.send("S", [100.0 + i], 20000 + i * 3000)   # each window lapses
    rt2.send("S", [200.0], 60000)
    rt2.send("S", [201.0], 60100)     # within 1s: e2
    rt2.send("S", [202.0], 60200)     # e3 → match
    rt2.flush()
    assert [200.0, 201.0, 202.0] in [list(r) for r in rows2]

    # non-chain shape (logical state) with element within still falls back
    import pytest as _pytest
    from siddhi_tpu.tpu.expr_compile import DeviceCompileError as _DCE
    with _pytest.raises(_DCE):
        DeviceNFARuntime("""
        define stream A (v double);
        define stream B (v double);
        from (e1=A[v>1.0] and e2=B[v>1.0]) within 1 sec -> e3=A[v>2.0]
        select e3.v as c insert into O;
        """)


# -- the order-preserving pack (counts on the CPU backend, no times) ---------

PACK_P, PACK_N = 300, 40
PACK_DENSITIES = {"none": 0.0, "sparse": 0.05, "more_than_n": 0.5,
                  "full": 1.0}
PACK_DTYPES = ["bool", "int32", "int64", "float32"]


@pytest.mark.parametrize("lanes", [0, 5], ids=["single", "vmap"])
@pytest.mark.parametrize("dtype", PACK_DTYPES)
@pytest.mark.parametrize("density", list(PACK_DENSITIES))
def test_pack_first_equals_numpy_reference(density, dtype, lanes):
    """Slot c holds the (c+1)-th marked row, later slots the fill, marked
    rows past the n-th are dropped and counted — the table invariant of
    ``block_init_state``, leaf by leaf, alone and under ``vmap``."""
    import jax
    import numpy as np

    from siddhi_tpu.tpu.rowpack import pack_first

    rng = np.random.default_rng(
        [lanes, list(PACK_DENSITIES).index(density), PACK_DTYPES.index(dtype)])
    shape = (max(lanes, 1), PACK_P)
    mask = rng.random(shape) < PACK_DENSITIES[density]
    if dtype == "bool":
        vals, fill = rng.random(shape) < 0.5, False
    elif dtype == "float32":
        vals, fill = rng.random(shape).astype(np.float32), 0.0
    else:
        # beyond 32 bits where the leaf has them: a timestamp stays exact
        hi = 2**40 if dtype == "int64" else 2**31 - 1
        vals, fill = rng.integers(1, hi, shape).astype(dtype), -1

    def pack(m, v):
        return pack_first(m, PACK_N, {"leaf": v}, {"leaf": fill})

    if lanes:
        out = jax.jit(jax.vmap(pack))(mask, vals)
    else:
        out = jax.tree.map(lambda x: x[None], jax.jit(pack)(mask[0], vals[0]))
    taken, got, dropped = jax.tree.map(np.asarray, out)
    assert got["leaf"].dtype == vals.dtype
    for lane in range(shape[0]):
        marked = np.flatnonzero(mask[lane])
        want = np.full(PACK_N, fill, vals.dtype)
        want[:min(marked.size, PACK_N)] = vals[lane, marked[:PACK_N]]
        np.testing.assert_array_equal(got["leaf"][lane], want)
        np.testing.assert_array_equal(
            taken[lane], np.arange(PACK_N) < marked.size)
        assert int(dropped[lane]) == max(marked.size - PACK_N, 0)
    if density == "more_than_n":
        assert int(dropped.min()) > 0


CHAIN4 = """
define stream S (sym string, v double);
from every e1=S[v > 20.0] -> e2=S[sym == e1.sym and v > e1.v]
  -> e3=S[v > e2.v] -> e4=S[v > e3.v] within 5000
select e1.v as a, e2.v as b, e3.v as c, e4.v as d insert into O;
"""


def blocked_runtime(app, creation_cap=None, **sizes):
    """A blocked runtime whose step carries the optional creation budget
    (a compiler argument the one-query runtime does not pass on)."""
    import jax

    rt = DeviceNFARuntime(app, **sizes)
    assert rt.compiler.blocked
    if creation_cap is not None:
        rt.compiler.creation_cap = creation_cap
        rt.compiler._step = jax.jit(rt.compiler.make_step(),
                                    donate_argnums=(0,))
    return rt


@pytest.mark.parametrize("lanes", [0, 4], ids=["single", "vmap_lanes"])
@pytest.mark.parametrize("creation_cap", [None, 8],
                         ids=["exact_growth", "creation_budget"])
def test_blocked_step_compiles_to_no_scatter(lanes, creation_cap):
    """Every stage's survivor pack (and the creation budget's, where one is
    set) and the emitted rows' pack into the ``[B]`` row table (PR 34) are
    index-once, gather-n: the optimized HLO of the jitted step holds gathers
    and not one scatter, alone and vmapped over lanes."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    rt = blocked_runtime(CHAIN4, creation_cap, slot_capacity=16,
                         batch_capacity=32)
    nfa = rt.compiler
    b = rt.builder.emit()
    args = (nfa.init_state(), b["cols"], b["tag"], b["ts"],
            jnp.asarray(b["ts_base"]), jnp.asarray(np.int32(b["count"])))
    step = nfa.make_step()
    if lanes:
        step = jax.vmap(step)
        args = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x)[None],
                                       (lanes,) + jnp.shape(x)), args)
    text = jax.jit(step).lower(*args).compile().as_text()
    opcodes = re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(",
                         text, re.M)
    assert not re.search(r"\bscatter\b", text), sorted(set(opcodes))
    # the mechanism engaged: every waiting state's pack is one gather of
    # rows (the creation budget packs the seeds and each stage's creations
    # too), the last stage packs its emitted rows by one more, and no other
    # gather is left: a stage fetches by jstar inside its reduce
    packs = [g for g in _gathers(text) if "nfa.compact" in g[1]]
    assert len(packs) == nfa.S - 1
    emits = [g for g in _gathers(text) if "nfa.emit" in g[1]]
    assert len(emits) == 1 and f"nfa.stage{nfa.S - 1}" in emits[0][1]
    assert opcodes.count("gather") == \
        (2 if creation_cap is not None else 1) * (nfa.S - 1) + 1


def _gathers(hlo_text):
    """``(result type, scope)`` of every gather of an optimized HLO."""
    import re
    return re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) gather\(.*?op_name=\"([^\"]*)\"",
        hlo_text, re.M)


@pytest.mark.parametrize("lanes", [0, 4], ids=["single", "vmap_lanes"])
def test_plain_chain_gathers_nothing_by_jstar(lanes):
    """A plain keyed chain reads one thing of the event that advances a
    candidate, the state's new binding (the outputs' column at the last
    stage), and it rides the stage's reduce: the optimized HLO holds no
    gather with a 64-bit result (the dead ``ts[jstar]``, which a TPU runs as
    two) and none at all outside the survivor packs and the emitted rows'
    pack, alone and vmapped."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rt = blocked_runtime(CHAIN4, slot_capacity=16, batch_capacity=32)
    nfa = rt.compiler
    assert not nfa.is_sequence
    assert not any(st.within_ms is not None for st in nfa.states)
    b = rt.builder.emit()
    args = (nfa.init_state(), b["cols"], b["tag"], b["ts"],
            jnp.asarray(b["ts_base"]), jnp.asarray(np.int32(b["count"])))
    step = nfa.make_step()
    if lanes:
        step = jax.vmap(step)
        args = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x)[None],
                                       (lanes,) + jnp.shape(x)), args)
    gathers = _gathers(jax.jit(step).lower(*args).compile().as_text())
    assert len(gathers) == nfa.S
    assert not [g for g in gathers if g[0][1:3] == "64"], gathers
    assert all("nfa.compact" in g[1] or "nfa.emit" in g[1]
               for g in gathers), gathers


def test_creation_budget_counts_what_it_drops_and_keeps_the_oldest():
    """``creation_cap`` is the same pack at K slots: with room it changes
    nothing, without it the newest creations of a stage drop and are
    counted."""
    app = """
    define stream S (v double);
    from every e1=S[v > 0.0] -> e2=S[v > 1000.0] -> e3=S[v > 2000.0]
    select e1.v as a, e2.v as b, e3.v as c insert into O;
    """
    events = [("S", [float(i + 1)], 1000 + i) for i in range(12)] + \
        [("S", [1500.0], 1100), ("S", [2500.0], 1101)]

    def run(cap):
        rt = blocked_runtime(app, cap, slot_capacity=32, batch_capacity=16)
        rows = []
        rt.add_callback(rows.extend)
        for sid, row, ts in events:
            rt.send(sid, row, ts)
        rt.flush()
        return sorted(list(r) for r in rows), rt.drop_count

    exact, drops = run(None)
    assert drops == 0 and len(exact) == 12
    assert run(14) == (exact, 0)
    # 12 seeds, then the 1500.0 event itself seeds: the budget of 5 keeps
    # the five oldest creations of the batch
    capped, drops = run(5)
    assert capped == exact[:5] and drops > 0


# -- what a stage fetches from the advancing event (PR 32) --------------------

FETCH_DTYPES = ["float32", "int32", "int64", "float64", "bool"]


def _fetch_leaf(rng, dtype, n):
    """A [n] leaf of ``dtype`` with the bit patterns a value-level move
    would lose: NaNs with payloads, -0.0, numbers beyond 32 bits."""
    import numpy as np

    if dtype == "bool":
        return rng.random(n) < 0.5
    if dtype.startswith("float"):
        bits = np.dtype(dtype).itemsize * 8
        raw = rng.integers(0, 2**(bits - 1), n, dtype=np.uint64).astype(
            f"uint{bits}")
        leaf = raw.view(dtype).copy()
        leaf[0] = -0.0
        # a quiet and a signalling NaN, payloads set
        leaf[1:3] = np.array(
            [0x7FC00123, 0x7F800456] if bits == 32
            else [0x7FF8000000000123, 0x7FF0000000000456],
            f"uint{bits}").view(dtype)
        return leaf
    hi = 2**40 if dtype == "int64" else 2**31 - 1
    return rng.integers(-hi, hi, n).astype(dtype)


@pytest.mark.parametrize("lanes", [0, 3], ids=["single", "vmap"])
@pytest.mark.parametrize("width", [1, 5], ids=["W1", "W5"])
@pytest.mark.parametrize("dtype", FETCH_DTYPES)
def test_first_hit_equals_numpy_reference(dtype, width, lanes):
    """``first_hit`` = NumPy's ``any`` / ``argmax`` over the grid and
    ``leaf[jstar]`` bit for bit, leaf by leaf, columns with no hit included
    (they read event 0, as ``leaf[argmax]`` does), alone and under ``vmap``."""
    import jax
    import numpy as np

    from siddhi_tpu.tpu.nfa_block import first_hit

    B, P = 24, 70
    rng = np.random.default_rng([FETCH_DTYPES.index(dtype), width, lanes])
    L = max(lanes, 1)
    grid = rng.random((L, B, P)) < 0.08
    grid[:, :, :5] = False                         # columns with no hit
    grid[:, 0, 5] = True                           # a hit on event 0
    # `width` 32-bit words in all: 64-bit leaves are two words each
    per = 2 if dtype in ("int64", "float64") else 1
    n_leaves = max(width // per, 1)
    vals = {f"k{i}": np.stack([_fetch_leaf(rng, dtype, B) for _ in range(L)])
            for i in range(n_leaves)}

    if lanes:
        out = jax.jit(jax.vmap(first_hit))(grid, vals)
    else:
        out = jax.tree.map(
            lambda x: x[None],
            jax.jit(first_hit)(grid[0], {k: v[0] for k, v in vals.items()}))
    adv, jstar, got = jax.tree.map(np.asarray, out)
    np.testing.assert_array_equal(adv, grid.any(axis=1))
    np.testing.assert_array_equal(jstar, grid.argmax(axis=1))
    assert not adv[:, :5].any() and (jstar[:, :5] == 0).all()
    uint = {1: np.uint8, 4: np.uint32, 8: np.uint64}
    for k, v in vals.items():
        assert got[k].dtype == v.dtype and got[k].shape == (L, P)
        for lane in range(L):
            want = v[lane][grid[lane].argmax(axis=0)]
            u = uint[v.dtype.itemsize]
            np.testing.assert_array_equal(got[k][lane].view(u), want.view(u))


def test_first_hit_without_leaves_fetches_nothing():
    import jax
    import numpy as np

    from siddhi_tpu.tpu.nfa_block import first_hit

    grid = np.zeros((6, 9), bool)
    grid[4, 2] = grid[5, 2] = True
    adv, jstar, got = jax.jit(lambda g: first_hit(g, {}))(grid)
    assert got == {} and bool(adv[2]) and int(jstar[2]) == 4
    text = jax.jit(lambda g: first_hit(g, {})).lower(grid).compile().as_text()
    assert " gather(" not in text


PLAN_FEATURES = {
    # what the plan states -> what a stage fetches of the advancing event
    "plain": ("""
        define stream S (sym string, v double);
        from every e1=S[v > 20.0] -> e2=S[sym == e1.sym and v > e1.v]
          -> e3=S[v > e2.v] within 6000
        select e1.sym as s, e1.v as a, e2.v as b, e3.v as c insert into O;
        """, dict(seq=False, ew=False)),
    "sequence": ("""
        define stream S (sym string, v double);
        from every e1=S[v > 10.0], e2=S[v > e1.v], e3=S[v > e2.v]
        select e1.v as a, e2.v as b, e3.v as c insert into O;
        """, dict(seq=True, ew=False)),
    "element_within": ("""
        define stream S (sym string, v double);
        from every e1=S[v > 20.0] -> e2=S[v > e1.v] within 120
          -> e3=S[v > e2.v] within 200 -> e4=S[v > e3.v]
        select e1.v as a, e2.v as b, e3.v as c, e4.v as d insert into O;
        """, dict(seq=False, ew=True)),
    "two_attributes_bound": ("""
        define stream S (sym string, v double);
        from every e1=S[v > 20.0] -> e2=S[v > e1.v]
          -> e3=S[sym == e2.sym and v > e2.v] -> e4=S[v > e3.v] within 6000
        select e1.v as a, e2.sym as s2, e2.v as b, e3.v as c, e4.sym as s4,
               e4.v + e1.v as d insert into O;
        """, dict(seq=False, ew=False)),
    "single_state": ("""
        define stream S (sym string, v double);
        from every e1=S[v > 35.0]
        select e1.sym as s, e1.v as a insert into O;
        """, dict(seq=False, ew=False)),
}


@pytest.mark.parametrize("batch", [64, 7], ids=["one_batch", "small_batches"])
@pytest.mark.parametrize("feature", list(PLAN_FEATURES))
def test_plan_features_match_the_interpreter(feature, batch):
    """Each thing a stage may fetch by ``jstar`` (a new binding, two of them,
    the rank in a sequence, the time under element-level `within`, the
    output's event columns) against the scalar interpreter, and the step's
    outputs hold the count ``n`` and ``mask``, ``j`` and the output columns
    twice, packed ``[B]`` and whole ``[P]`` (once where one state emits
    ``[B]`` as it is), and nothing else."""
    import jax.numpy as jnp
    import numpy as np

    app, plan = PLAN_FEATURES[feature]
    events = gen_one_stream(140, 50 + list(PLAN_FEATURES).index(feature))
    rows, rt = device(app, events, slot_capacity=64, batch_capacity=batch)
    nfa = rt.compiler
    assert nfa.is_sequence == plan["seq"]
    assert any(st.within_ms is not None for st in nfa.states) == plan["ew"]
    assert rt.drop_count == 0
    want = oracle(app, events)
    assert len(want) > 3
    assert_rows_match(want, rows)

    # the tables carry what the plan reads and nothing for the rest
    for tbl in nfa.init_state()["tables"].values():
        assert ("last_ts" in tbl) == plan["ew"]
    b = rt.builder.emit()
    _, ys = nfa.make_step()(
        nfa.init_state(), b["cols"], b["tag"], b["ts"],
        jnp.asarray(b["ts_base"]), jnp.asarray(np.int32(b["count"])))
    table = {"mask", "j"} | {name for name, _, _ in nfa.out_specs}
    full = ys.pop("full", None)
    assert set(ys) == {"n"} | table
    assert ys.pop("n").shape == () and nfa.M == nfa.B
    assert all(v.shape == (nfa.B,) for v in ys.values())
    assert (full is None) == (nfa.S == 1)
    if full is not None:
        assert set(full) == table
        assert all(v.shape == ((nfa.S - 1) * nfa.C + nfa.B,)
                   for v in full.values())


# -- the emitted rows, packed on the device (PR 34) ---------------------------
# The step hands out its rows twice: packed to the front of a [B] table (what
# the host fetches) and as the last stage's whole [P] candidate table (what it
# fetched until PR 34, read now only for a batch that emitted more than B).

def _seq_events(n, seed):
    rng = random.Random(seed)
    return [("S", [round(rng.uniform(0, 30), 1)], 1000 + i * 50)
            for i in range(n)]


PACKED_CORPUS = {
    "chain3": (CHAIN3, gen_one_stream),
    "chain4": (CHAIN4, gen_one_stream),
    "two_stream": (TWO_STREAM, gen_two_stream),
    "sequence2": (SEQ2, _seq_events),
    **{f"plan_{k}": (app, gen_one_stream)
       for k, (app, _) in PLAN_FEATURES.items()},
}


def _lane_batches(app, events_by_lane, slots, batch):
    """``(compiler, [[wire batch of lane l] for each step])``: one builder a
    lane, every lane the same number of events, so the lanes' batches line
    up step for step (the last one partial)."""
    rts = [DeviceNFARuntime(app, slot_capacity=slots, batch_capacity=batch)
           for _ in events_by_lane]
    steps = []
    n = len(events_by_lane[0])
    for i in range(n):
        for rt, events in zip(rts, events_by_lane):
            rt.builder.append(*events[i])
        if rts[0].builder.full or i == n - 1:
            steps.append([rt.builder.emit() for rt in rts])
    return rts[0], steps


def _wire(b):
    import numpy as np
    return (b["cols"], b["tag"], b["ts"], np.int64(b["ts_base"]),
            np.int32(b["count"]))


def _check_tables(rt, ys, lane_batch=None):
    """One step's outputs: the whole candidate table's decode (the step's
    own ``[B]`` where one state emits it as it is), after holding the other
    readings to it: ``n`` counts its rows (a lane); ``decode_rows`` gives
    them whichever table it reads; the packed table holds them all, at its
    front and in order, wherever no lane emitted more than ``M``, and the
    first ``M`` of a lane that did."""
    import numpy as np
    from siddhi_tpu.tpu.nfa import decode_rows

    nfa = rt.compiler
    full = nfa.decode_outputs(ys.get("full", ys), lane_batch)
    n = np.asarray(ys["n"])
    assert n.dtype == np.int32 and int(n.sum()) == len(full)
    assert_same_chunk(full, decode_rows(rt, ys, lane_batch))
    packed = nfa.decode_outputs(ys, lane_batch)
    if n.max() <= nfa.M:
        assert_same_chunk(full, packed)
    if "full" in ys:
        mask = np.asarray(ys["mask"])
        assert mask.shape[-1] == nfa.M
        taken = np.minimum(n, nfa.M)
        assert np.array_equal(
            mask, np.arange(nfa.M) < np.expand_dims(taken, -1))
    return full


@pytest.mark.parametrize("batch", [32, 7], ids=["B32", "B7"])
@pytest.mark.parametrize("name", list(PACKED_CORPUS))
def test_the_packed_rows_are_the_full_tables_rows(name, batch):
    """Batch after batch from the same carried state, one lane alone and
    three lanes stacked under ``vmap``: the rows decoded from the packed
    ``[B]`` table are the rows decoded from the whole ``[P]`` candidate
    table, element for element and in order; ``n`` counts them; the stacked
    decode is the lanes' decodes one after another. Batches of 7 events
    emit more than 7 rows now and then: those are read from the whole
    table, and only those."""
    import jax
    import numpy as np

    app, gen = PACKED_CORPUS[name]
    lanes = [gen(100, 60 + lane) for lane in range(3)]
    rt, steps = _lane_batches(app, lanes, slots=24, batch=batch)
    nfa = rt.compiler
    assert nfa.blocked and nfa.M == nfa.B == batch
    vstep = jax.jit(jax.vmap(nfa.make_step()))
    stacked = jax.tree.map(lambda *xs: np.stack(xs),
                           *[nfa.init_state() for _ in lanes])
    single = [nfa.init_state() for _ in lanes]
    n_rows = n_over = 0
    for batches in steps:
        by_lane = []
        for lane, b in enumerate(batches):
            single[lane], ys = nfa._step(single[lane], *_wire(b))
            rt.parts = None
            by_lane.append(_check_tables(rt, ys))
            over = "full" in ys and len(by_lane[-1]) > nfa.M
            assert ("decode_full" in (rt.parts or {})) == over
            n_over += over
        stacked, ys = vstep(stacked, *jax.tree.map(
            lambda *xs: np.stack(xs), *[_wire(b) for b in batches]))
        assert np.asarray(ys["n"]).tolist() == [len(c) for c in by_lane]
        rows = _check_tables(rt, ys, lane_batch=nfa.B)
        assert rows.rows() == [r for c in by_lane for r in c.rows()]
        n_rows += len(rows)
    assert n_rows > 5
    assert not (n_over and batch == 32)


@functools.lru_cache(maxsize=None)
def _blocked_shapes(n):
    """Seeded random chains of ``test_nfa_fuzz`` that take the blocked
    kernel (patterns and sequences, one or two streams, ``within``)."""
    from test_nfa_fuzz import START, _chain, _events
    from siddhi_tpu.tpu.expr_compile import DeviceCompileError
    out, seed = [], 0
    while len(out) < n:
        rng = random.Random(3400 + seed)
        seed += 1
        app, two = _chain(rng)
        try:
            rt = DeviceNFARuntime(app, slot_capacity=8, batch_capacity=8,
                                  start_time=START)
        except DeviceCompileError:
            continue
        if rt.compiler.blocked:
            out.append((app, _events(rng, 60, two), rng.choice([8, 16, 32])))
    return out


@pytest.mark.parametrize("case", range(10))
def test_the_packed_rows_are_the_full_tables_rows_seeded_sweep(case):
    """The same over random blocked shapes: whichever table a batch's rows
    are read from (``decode_rows`` chooses by ``n``), they are the whole
    candidate table's rows in its order, and the packed table's wherever it
    holds them all."""
    app, events, batch = _blocked_shapes(10)[case]
    rt, steps = _lane_batches(app, [events], slots=64, batch=batch)
    nfa = rt.compiler
    state = nfa.init_state()
    for (b,) in steps:
        state, ys = nfa._step(state, *_wire(b))
        _check_tables(rt, ys)
    assert int(state["drops"]) == 0


OVERFLOW = """
define stream S (v double);
{device}
from every e1=S[v > 0.0] -> e2=S[v > 1000.0]
select e1.v as a, e2.v as b insert into O;
"""


def _overflow_events():
    # 30 partials wait in a table of 32; ONE event closes them all, in a
    # batch of 8: 30 rows where the packed table holds 8
    return [("S", [float(i + 1)], 1000 + i) for i in range(30)] + \
        [("S", [1500.0], 1100)] + \
        [("S", [float(i + 1)], 1200 + i) for i in range(5)]


def test_a_batch_that_emits_more_than_the_packed_table_reads_the_full_one():
    """No static table under ``P`` rows is a bound (every candidate may
    emit in one batch): the decode sees ``n > M`` and reads the whole
    candidate table, every row delivered and equal to the interpreter's,
    nothing counted as a drop, one ``decode_full`` recorded."""
    events = _overflow_events()
    want = oracle(OVERFLOW.format(device=""), events)
    assert len(want) == 30

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(OVERFLOW.format(
            device="@device(strict='true', batch='8', slots='32')"),
            playback=True)
        got = []
        rt.add_callback("O", StreamCallback(
            lambda evs: got.extend(e.data for e in evs)))
        rt.start()
        r = rt.device_bridges[0].runtime
        counts = []
        inner = r._decode
        r._decode = lambda ys: (counts.append(int(ys["n"])), inner(ys))[1]
        for sid, row, ts in events:
            rt.input_handler(sid).send(row, timestamp=ts)
        rt.flush_device()
        assert r.compiler.M == 8 and max(counts) == 30
        assert sum(c > 8 for c in counts) == 1
        assert_rows_match(want, got)
        assert [tuple(r_) for r_ in got] == [tuple(w) for w in want]
        assert r.drop_count == 0 and r.match_count == 30
        phases = rt.device_bridges[0].probe.phases
        full, decode = (phases.trackers[k].hist.count
                        for k in ("decode_full", "egress_decode"))
        # event-weighted, like every phase: the one batch's 8 events
        assert full == 8 and decode == len(events)
        rep = rt.observability.latency_report()["queries"]
        (entry,) = rep.values()
        assert "decode_full" in entry["phases"]
        assert entry["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-6)
    finally:
        m.shutdown()


# -- `within` as an int32 limit a candidate computes once ---------------------
# A stage tests the wire's int32 delta ``d[j]`` against ``lim[p] =
# clip(first_ts[p] - ts_base + within, -1, 2^31-1)``: each case sits on an
# edge of that arithmetic, its batches flushed as listed, against the scalar
# interpreter and the columnar host engine (``HostBlockNFA``, int64 times).

DAY = 86_400_000
WITHIN_PAIR = """
define stream S (v double);
from every e1=S[v > 20.0] -> e2=S[v > e1.v] within {within}
select e1.v as a, e2.v as b insert into O;
"""
WITHIN_ELEMENT = """
define stream S (v double);
from every e1=S[v > 10.0] -> e2=S[v > e1.v] within 100 -> e3=S[v > e2.v]
select e1.v as a, e2.v as b, e3.v as c insert into O;
"""
WITHIN_EDGES = {
    # exactly `within` after the first matches, 1 ms more does not
    "exact_edge": (WITHIN_PAIR.format(within=100), [
        [("S", [25.0], 1000), ("S", [30.0], 1100)],
        [("S", [40.0], 2000), ("S", [50.0], 2101)],
    ], [[25.0, 30.0]], [[40.0, 50.0]]),
    # a carried partial whose first_ts lies 2^32 ms before the batch's base:
    # its limit clamps to -1 (unclipped it would wrap to +500)
    "partial_far_before_base": (WITHIN_PAIR.format(within=1000), [
        [("S", [25.0], 1000)],
        [("S", [30.0], 1000 + 2**32 + 500), ("S", [40.0], 1100 + 2**32 + 500)],
    ], [[30.0, 40.0]], [[25.0, 30.0], [25.0, 40.0]]),
    # `within 30 days` passes 2^31 ms: a creation's limit clamps to
    # 2^31-1, a carried one does not, and 31 days later nothing matches
    "within_past_2_31": (WITHIN_PAIR.format(within="30 days"), [
        [("S", [25.0], 1000)],
        [("S", [30.0], 1000 + 20 * DAY), ("S", [35.0], 1005 + 20 * DAY)],
        [("S", [40.0], 1005 + 51 * DAY)],
    ], [[25.0, 30.0], [30.0, 35.0]], [[35.0, 40.0]]),
    # element-level: the gap since the previous element's bind, at its edge
    "element_edge": (WITHIN_ELEMENT, [
        [("S", [11.0], 1000), ("S", [12.0], 1100)],
        [("S", [13.0], 5000)],
        [("S", [20.0], 6000), ("S", [21.0], 6101), ("S", [22.0], 6150)],
    ], [[11.0, 12.0, 13.0]], [[20.0, 21.0, 22.0]]),
    # one batch whose deltas reach 2^31-1: the largest delta the wire
    # carries, one past `within` of the first seed and on it for the second
    "delta_span_near_2_31": (WITHIN_PAIR.format(within=2**31 - 2), [
        [("S", [25.0], 1000), ("S", [26.0], 1001),
         ("S", [30.0], 1000 + 2**31 - 1)],
    ], [[26.0, 30.0]], [[25.0, 30.0]]),
}


def _device_batches(app, batches):
    rt = DeviceNFARuntime(app, slot_capacity=16, batch_capacity=8)
    assert rt.compiler.blocked
    rows = []
    rt.add_callback(rows.extend)
    for batch in batches:
        for sid, row, ts in batch:
            rt.send(sid, row, ts)
        rt.flush()
    assert rt.drop_count == 0 and rt.builder.ts_clamped == 0
    return [list(r) for r in rows]


def _host_block_batches(app, batches):
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:host_batch(batch='8', strict='true')\n" + app,
            playback=True)
        assert [b.kind for b in rt.host_bridges] == ["host_nfa"]
        got = []
        rt.add_callback("O", StreamCallback(
            lambda evs: got.extend(list(e.data) for e in evs)))
        rt.start()
        ih = rt.input_handler("S")
        for batch in batches:
            ih.send_rows([row for _, row, _ in batch],
                         [ts for _, _, ts in batch])
        rt.shutdown()
    finally:
        m.shutdown()
    return got


@pytest.mark.parametrize("case", list(WITHIN_EDGES))
def test_within_at_the_edges_of_the_int32_limit(case):
    app, batches, held, absent = WITHIN_EDGES[case]
    want = oracle(app, [e for batch in batches for e in batch])
    for row in held:
        assert row in want
    for row in absent:
        assert not any(w[:len(row)] == row for w in want)
    assert_rows_match(want, _device_batches(app, batches))
    assert_rows_match(want, _host_block_batches(app, batches))


@pytest.mark.parametrize("lanes", [0, 4], ids=["single", "vmap_lanes"])
@pytest.mark.parametrize("app", ["stream_within", "element_within"])
def test_no_op_of_a_stage_grid_is_64_bit(app, lanes):
    """The lowered step of a chain with a `within` holds no array of a
    64-bit element type at any stage grid's ``[B, P]`` shape (an int64 test
    there runs as word pairs with a borrow on a v5e), while the grids
    themselves are there, alone and vmapped over lanes."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    text = CHAIN4 if app == "stream_within" else \
        PLAN_FEATURES["element_within"][0]
    rt = blocked_runtime(text, slot_capacity=16, batch_capacity=32)
    nfa = rt.compiler
    assert (nfa.within is not None) == (app == "stream_within")
    assert any(st.within_ms is not None for st in nfa.states) == \
        (app == "element_within")
    b = rt.builder.emit()
    args = (nfa.init_state(), b["cols"], b["tag"], b["ts"],
            jnp.asarray(b["ts_base"]), jnp.asarray(np.int32(b["count"])))
    step = nfa.make_step()
    if lanes:
        step = jax.vmap(step)
        args = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x)[None],
                                       (lanes,) + jnp.shape(x)), args)
    low = jax.jit(step).lower(*args).as_text()
    lead = f"{lanes}x" if lanes else ""
    grids = [f"{lead}{nfa.B}x{s * nfa.C + nfa.B}" for s in range(1, nfa.S)]
    for g in grids:
        assert f"tensor<{g}xi1>" in low, g
    wide = re.findall(r"tensor<([\dx]+)x(?:i64|ui64|f64)>", low)
    assert not [w for w in wide if w in grids], sorted(set(wide))
