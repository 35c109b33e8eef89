"""The batch compaction at the head of a windowed step (PR 38,
``tpu/rowpack.py`` ``compact_front``): accepted events go to the front of
their batch in their order, by ONE row gather and only where the mask is not
a prefix already. Held against the rule it replaced, written out in NumPy:
``out[rank[mask]] = x[mask]``, the column's fill elsewhere."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu import DeviceStreamRuntime
from siddhi_tpu.tpu.query_compile import _TS_POS
from siddhi_tpu.tpu.rowpack import compact_front

B = 16
MASKS = {
    "all_true": np.ones(B, bool),
    "empty": np.zeros(B, bool),
    "partial_prefix": np.arange(B) < 11,
    "suffix": np.arange(B) >= 5,
    "alternating": np.arange(B) % 2 == 0,
    "only_last": np.arange(B) == B - 1,
    "all_but_first": np.arange(B) > 0,
}
PREFIXES = ("all_true", "empty", "partial_prefix")


def _bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 4: np.uint32, 8: np.uint64}[a.itemsize])


def _payloads(dtype):
    """[B] values of ``dtype`` with the payloads a move must not touch, and
    the fills a step hands that dtype (zero; the clocks' ``_TS_POS``; a
    min / max identity)."""
    rng = np.random.default_rng(3)
    if dtype == np.float32:
        x = rng.standard_normal(B).astype(np.float32)
        x[1], x[4] = -0.0, np.inf
        # two NaNs that differ in their payload bits
        x.view(np.uint32)[[2, B - 1]] = (0x7FC01234, 0xFFC00001)
        return x, (np.float32(0), np.float32(np.inf))
    if dtype == np.int64:
        x = rng.integers(-2 ** 62, 2 ** 62, B, dtype=np.int64)
        x[0], x[B - 1] = 2 ** 40 + 7, -1
        return x, (np.int64(0), np.int64(_TS_POS))
    if dtype == np.int32:
        x = rng.integers(-2 ** 31, 2 ** 31 - 1, B, dtype=np.int32)
        return x, (np.int32(0), np.int32(np.iinfo(np.int32).max))
    return rng.random(B) < 0.5, (np.bool_(False),)


def _old_rule(x, mask, fill):
    out = np.full(x.shape, fill, x.dtype)
    rank = np.cumsum(mask) - 1
    out[rank[mask]] = x[mask]
    return out


@pytest.mark.parametrize("mask_name", list(MASKS))
@pytest.mark.parametrize("dtype", [np.bool_, np.int32, np.int64, np.float32],
                         ids=["bool", "int32", "int64", "f32"])
def test_compact_front_is_the_old_scatter_bit_for_bit(dtype, mask_name):
    mask = MASKS[mask_name]
    x, fill_list = _payloads(dtype)
    other = np.arange(B, dtype=np.int64) * 3 - 5
    for fill in fill_list:
        # two leaves of different widths in one call: one row gather for both
        vals = {"x": jnp.asarray(x), "rows": [jnp.asarray(other)]}
        fills = {"x": fill, "rows": [7]}
        got, k, moved = jax.jit(compact_front)(jnp.asarray(mask), vals, fills)
        want = _old_rule(x, mask, fill)
        assert np.asarray(got["x"]).dtype == want.dtype
        assert (_bits(got["x"]) == _bits(want)).all()
        assert (np.asarray(got["rows"][0])
                == _old_rule(other, mask, np.int64(7))).all()
        assert int(k) == int(mask.sum())
        assert bool(moved) == (mask_name not in PREFIXES)


NO_FILTER = """
define stream S (g long, v long, w double);
from S#window.length(5)
select g, sum(v) as t, max(w) as top, count() as n
insert into O;
"""


@pytest.mark.parametrize("mask_name", list(MASKS))
def test_a_step_handed_any_valid_mask_gives_the_rows_of_the_packed_batch(
        mask_name):
    """``step(state, cols, ts, valid)`` promises no prefix: whatever mask it
    is handed, it gives what it gives for the same events already packed to
    the front (which is what the bridge hands it, and what the scatter
    gave)."""
    mask = MASKS[mask_name]
    rng = np.random.default_rng(11)
    rt = DeviceStreamRuntime(NO_FILTER, batch_capacity=B)
    compiled = rt.compiled
    cols = {"g": rng.integers(0, 4, B).astype(np.int64),
            "v": rng.integers(-50, 50, B).astype(np.int64),
            "w": rng.standard_normal(B)}
    wire = rt.builder.emit()["cols"]        # the bridge's column dtypes
    cols = {n: c.astype(wire[n].dtype) for n, c in cols.items()}
    ts = 1000 + np.arange(B, dtype=np.int64)
    k = int(mask.sum())
    front = np.concatenate([np.flatnonzero(mask), np.flatnonzero(~mask)])
    packed = {"cols": {n: c[front] for n, c in cols.items()},
              "ts": ts[front], "valid": np.arange(B) < k}
    # a first batch fills the window, so that the second slides it
    warm = {"cols": cols, "ts": ts - 100, "valid": np.ones(B, bool)}
    rows = []
    for batch in ({"cols": cols, "ts": ts, "valid": mask}, packed):
        state, _ = compiled.step(compiled.init_state(), warm)
        state, out = compiled.step(state, batch)
        rows.append((compiled.decode_outputs(out).rows(), state))
    (got, state), (want, state_packed) = rows
    assert len(got) == k and got == want
    moves = int(state.pop("compact_moves"))
    assert moves == (mask_name not in PREFIXES)
    assert int(state_packed.pop("compact_moves")) == 0
    jax.tree.map(np.testing.assert_array_equal, state, state_packed)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("config,conds", [
    ("benchmark/configs/nexmark-q5.small.siddhi", 1),
    ("benchmark/configs/window-groupby.siddhi", 1),
])
def test_the_benchmarks_steps_scatter_no_64_bit_element_and_branch_once(
        config, conds):
    """The two served configurations that compact: their step holds ONE
    ``cond`` (the compaction's) and no scatter of a 64-bit operand."""
    import pathlib
    text = (pathlib.Path(__file__).parent.parent / config).read_text()
    m = SiddhiManager()
    try:
        r = m.create_siddhi_app_runtime(
            text, playback=True).device_bridges[0].runtime
        b = r.builder.emit()
        jaxpr = jax.make_jaxpr(r.compiled._make_step())(
            r.state, b["cols"], b["ts"], b["valid"])
    finally:
        m.shutdown()
    eqns = list(_eqns(jaxpr.jaxpr))
    for e in eqns:
        if e.primitive.name.startswith("scatter"):
            assert e.invars[0].aval.dtype.itemsize < 8, e
    assert sum(e.primitive.name == "cond" for e in eqns) == conds


HOPPING = """
define stream Bid (auction long, bidder long, price long);
from Bid#window.hopping(1000, 200)
select auction, count() as num group by auction order by num desc limit 1
insert into HotItems;
"""
FILTERED = """
define stream S (v long);
from S[v > 0]#window.length(4) select sum(v) as s insert into O;
"""


def test_compact_moves_counts_the_steps_whose_batch_was_moved():
    # no filter: the bridge's `valid` is a prefix, nothing ever moves
    hop = DeviceStreamRuntime(HOPPING, batch_capacity=8, window_capacity=64)
    hop.add_callback(lambda rows: None)
    for i in range(40):
        hop.send([i % 3, 1, 1], timestamp=1000 + 30 * i)
    hop.flush()
    assert int(hop.state["compact_moves"]) == 0
    assert hop.step_gauges["compact_moves"] == 0
    assert hop.step_gauges["window_live_keys"] == 3

    # a filter that rejects a row inside every batch: every step moves
    app_rows = [1, -1, 2, 3] * 5
    rt = DeviceStreamRuntime(FILTERED, batch_capacity=4)
    got = []
    rt.add_callback(got.extend)
    for i, v in enumerate(app_rows[:12]):
        rt.send([v], timestamp=i)
    assert int(rt.state["compact_moves"]) == 3
    assert rt.step_gauges["compact_moves"] == 3     # on_drained read it

    snap = rt.snapshot_state()
    rt2 = DeviceStreamRuntime(FILTERED, batch_capacity=4)
    got2 = []
    rt2.add_callback(got2.extend)
    rt2.restore_state(snap)
    for i, v in enumerate(app_rows[12:]):
        rt2.send([v], timestamp=12 + i)
    assert int(rt2.state["compact_moves"]) == 5
    assert rt2.step_gauges["compact_moves"] == 5
    # the window slid through the restore: 2+3+1, 3+1+2, 1+2+3 ...
    assert [r[0] for r in got2] == [7, 8, 9, 7, 8, 9]

    # a snapshot from before the counter restores, and counts from zero
    old = rt.snapshot_state()
    del old["device"]["compact_moves"]
    rt3 = DeviceStreamRuntime(FILTERED, batch_capacity=4)
    rt3.add_callback(lambda rows: None)
    rt3.restore_state(old)
    for i, v in enumerate(app_rows[:4]):
        rt3.send([v], timestamp=100 + i)
    assert int(rt3.state["compact_moves"]) == 1


def test_the_served_query_shows_compact_moves_beside_its_steps():
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "define stream S (v long);\n"
            "@device(strict='true', batch='4')\n"
            "from S[v > 0]#window.length(4) select sum(v) as s "
            "insert into O;", playback=True)
        got = []
        rt.add_callback("O", StreamCallback(lambda evs: got.extend(evs)))
        rt.start()
        for i, v in enumerate([1, -1, 2, 3] * 3):
            rt.input_handler("S").send([v], timestamp=i)
        rt.flush_device()
        bridge = rt.device_bridges[0]
        assert bridge.runtime.step_gauges == {"compact_moves": 3}
        assert bridge.probe.steps == 3
        entry = rt.observability.latency_report()["queries"][
            bridge.query_name]
        assert entry["step"] == {"compact_moves": 3}
        report = rt.ctx.statistics_manager.report()
        assert any(k.endswith(".compact_moves")
                   for section in report.values() if isinstance(section, dict)
                   for k in section)
        assert len(got) == 9
    finally:
        m.shutdown()
