"""Differential fuzz: randomized query shapes × randomized streams, host
interpreter vs device kernels on identical inputs.

The corpora pin *known* reference behaviors; this sweep hunts UNKNOWN
divergences by sampling the cross product the hand-written suites cannot
cover: window type × aggregate set × filter × batch capacity × data
distribution. Seeds are fixed — failures reproduce exactly. A shape the
device compiler rejects (host-only surface) counts as covered fallback, not
a failure; the test asserts a minimum share of shapes actually ran on
device so silent coverage regressions fail loudly."""

import random

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu import DeviceCompileError, DeviceStreamRuntime
from util_parity import rows_equal

# batch() is deliberately absent: it is CHUNK-defined (the device batch is
# the chunk), so a per-event host feed is not comparable — the chunk-aligned
# corpus test (test_tpu_query.test_parity_batch_chunk_aligned) covers it
WINDOWS = [
    "", "#window.length({n})", "#window.lengthBatch({n})",
    "#window.time({ms})", "#window.timeBatch({ms})",
    "#window.timeLength({ms}, {n})", "#window.session({ms})",
    "#window.sort({n}, v)", "#window.sort({n}, v, 'desc')",
    "#window.hopping({ms}, {hop})", "#window.frequent({n}, sym)",
    "#window.lossyFrequent(0.3, 0.08, sym)",
]
AGG_SETS = [
    "sum(v) as s, count() as c",
    "sum(v) as s, avg(v) as a",
    "min(v) as mn, max(v) as mx, count() as c",
    "sum(p) as sp, stdDev(p) as sd",
    "count() as c",
]
FILTERS = ["", "[v > 20]", "[p < 75.0]", "[v > 10 and p > 5.0]"]


def _shape(rng):
    win = rng.choice(WINDOWS).format(
        n=rng.choice([2, 3, 5, 8]), ms=rng.choice([40, 90, 200]),
        hop=rng.choice([20, 50]))
    aggs = rng.choice(AGG_SETS)
    filt = rng.choice(FILTERS)
    if ("sort" in win or "frequent" in win) and ("min(" in aggs
                                                 or "stdDev" in aggs):
        aggs = "sum(v) as s, count() as c"   # host-only combos, keep density
    sel = f"sym, {aggs}" if "Batch" not in win and "hopping" not in win \
        else aggs
    return f"""
    define stream S (sym string, p double, v long);
    from S{filt}{win}
    select {sel}
    insert into O;
    """


def _events(rng, n):
    ts, out = 1000, []
    for _ in range(n):
        ts += rng.choice([1, 2, 5, 30, 120])
        out.append(([rng.choice("abcd"), round(rng.uniform(0, 100), 2),
                     rng.randrange(100)], ts))
    return out


def _host(app, events):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True)
    got = []
    rt.add_callback("O", StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    ih = rt.input_handler("S")
    for row, ts in events:
        ih.send(list(row), timestamp=ts)
    m.shutdown()
    return [e.data for e in got]


def _device(app, events, cap):
    rt = DeviceStreamRuntime(app, batch_capacity=cap)
    got = []
    rt.add_callback(got.extend)
    for row, ts in events:
        rt.send(list(row), timestamp=ts)
    rt.flush()
    return got


@pytest.mark.parametrize("seed", range(24))
def test_differential_fuzz(seed):
    rng = random.Random(1000 + seed)
    app = _shape(rng)
    events = _events(rng, rng.choice([40, 90]))
    cap = rng.choice([4, 8, 16, 64])
    try:
        actual = _device(app, events, cap)
    except DeviceCompileError:
        pytest.skip(f"host-only shape: {app.strip().splitlines()[1]}")
    expected = _host(app, events)
    assert len(expected) == len(actual), \
        f"row count {len(expected)} != {len(actual)} for app: {app}"
    for e, a in zip(expected, actual):
        assert rows_equal(e, a, rel=2e-3, abs_=2e-3), (app, e, a)


HOP_AGG_SETS = [a for a in AGG_SETS if "stdDev" not in a]
HOP_TAILS = ["", "", "order by {o} desc", "order by {o}", "limit 2",
             "order by {o} desc limit 1", "order by {o} limit 2 offset 1",
             "offset 1"]


def _grouped_hopping_shape(rng):
    """`hopping` meets `group by`: a grouped flush, and on it the selector's
    tail drawn from `order by` (the first aggregate, asc / desc), `limit`
    and `offset`."""
    aggs = rng.choice(HOP_AGG_SETS)
    group = rng.choice(["sym", "sym", "v", "sym, v"])
    tail = rng.choice(HOP_TAILS).format(o=aggs.split(" as ")[1].split(",")[0])
    win = f"hopping({rng.choice([40, 90, 200])}, {rng.choice([20, 50])})"
    return f"""
    define stream S (sym string, p double, v long);
    from S{rng.choice(FILTERS)}#window.{win}
    select {group}, {aggs}
    group by {group}
    {tail}
    insert into O;
    """


@pytest.mark.parametrize("seed", range(16))
def test_differential_fuzz_grouped_hopping(seed):
    rng = random.Random(7000 + seed)
    app = _grouped_hopping_shape(rng)
    events = _events(rng, rng.choice([40, 90]))
    cap = rng.choice([4, 8, 16, 64])
    actual = _device(app, events, cap)      # every drawn shape is served
    expected = _host(app, events)
    assert len(expected) == len(actual), \
        f"row count {len(expected)} != {len(actual)} for app: {app}"
    for e, a in zip(expected, actual):
        assert rows_equal(e, a, rel=2e-3, abs_=2e-3), (app, e, a)


def test_fuzz_device_coverage_share():
    """At least half the sampled shapes must compile on device — catches a
    silent regression that sends everything down the host fallback."""
    compiled = total = 0
    for seed in range(40):
        rng = random.Random(5000 + seed)
        app = _shape(rng)
        total += 1
        try:
            DeviceStreamRuntime(app, batch_capacity=8)
            compiled += 1
        except DeviceCompileError:
            pass
    assert compiled / total >= 0.5, f"device coverage {compiled}/{total}"
