"""One step protocol (ISSUE 31): the five served device runtimes are
``StepRuntime``s and take ``process`` / ``deliver`` / ``flush`` / ``collect``
from it, so a batch's seal → dispatch → fence → decode → deliver is the same
code whatever the plan. Pinned here on the CPU at small sizes: whose
functions the runtimes run, the ``phases`` record's keys, the rows against
the scalar interpreter, what a sync flush does behind the step, and that
``siddhi_tpu/tpu/`` stands without ``siddhi_tpu/flow/``.
"""

import ast
import os

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu.step_runtime import StepRuntime
from util_parity import rows_equal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STREAM_APP = """
define stream S (sym string, price double, vol long);
{device}
from S[price > 50.0]#window.length(4)
select sym, sum(vol) as total, count() as c, price insert into O;
"""

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
from L#window.length(1) join R#window.length(1) on L.k == R.k
select L.k as k, L.v as v, R.w as w insert into O;
"""

PARTITION_APP = """
define stream S (sym string, price double, vol long);
partition with (sym of S) begin
{device}
from every e1=S[price > 50.0] -> e2=S[price > e1.price]
    -> e3=S[price > e2.price] within 4000
select e1.price as p1, e2.price as p2, e3.price as p3 insert into O;
end;
"""

KEYED_APP = """
define stream S (sym string, price double, vol long);
partition with (sym of S) begin
{device}
from S[price > 20.0]#window.length(3)
select sym, max(price) as hi, sum(vol) as total, price
having hi > 60.0 insert into O;
end;
"""


# values exact in float32: the device computes DOUBLE in float32
def _s_events(n, seed):
    rng = np.random.default_rng(seed)
    return [("S", [f"k{int(rng.integers(4))}",
                   float(rng.integers(0, 400)) / 4,
                   int(rng.integers(1, 1000))], 1000 + i) for i in range(n)]


def _join_events(n, seed):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        k = f"k{int(rng.integers(3))}"
        if rng.random() < 0.5:
            out.append(("L", [k, int(rng.integers(100))], 1000 + i))
        else:
            out.append(("R", [k, float(rng.integers(0, 200)) / 2], 1000 + i))
    return out


# kind -> (app, events, batch, other @device options, rows keep the
# interpreter's order)
KINDS = {
    "stream": (STREAM_APP, _s_events(100, 5), 16, "", True),
    "nfa": (NFA_APP, _s_events(200, 6), 32, ", slots='16'", True),
    "join": (JOIN_APP, _join_events(100, 9), 8, "", True),
    # a lane orders its own keys' matches, the interpreter orders all of them
    "partition": (PARTITION_APP, _s_events(200, 7), 64,
                  ", slots='32', lanes='4'", False),
    # a keyed window (kind 'partition' too): a batch's rows in slot order
    "keyed": (KEYED_APP, _s_events(200, 8), 32, ", keys='8'", False),
}
BRIDGE_KIND = {"keyed": "partition"}

# the names ISSUE 31's acceptance keeps out of the four class bodies
SHARED = ("process", "deliver", "collect", "_fence", "_emit_batch",
          "_timed_process", "observe_step")


@pytest.fixture
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def _deploy(manager, app, device):
    rt = manager.create_siddhi_app_runtime(app.format(device=device),
                                           playback=True)
    got = []
    rt.add_callback("O", StreamCallback(
        lambda evs: got.extend((e.timestamp, e.data) for e in evs)))
    return rt, got


def _feed(rt, events):
    for sid, row, ts in events:
        rt.input_handler(sid).send(list(row), timestamp=ts)


def _tap_phases(runtime):
    """Every ``phases`` record the runtime hands its probe."""
    seen = []
    inner = runtime.step_observer

    def observer(n_events, latency_s, device_path=True, phases=None):
        seen.append(phases)
        inner(n_events, latency_s, device_path, phases=phases)

    runtime.step_observer = observer
    return seen


_PHASE_KEYS = {}    # mode -> (kind, key set) of the first kind that ran


@pytest.mark.parametrize("mode", ["sync", "async"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_a_served_runtime_runs_the_one_protocol(manager, kind, mode):
    app, events, batch, options, ordered = KINDS[kind]
    ref_rt, ref = _deploy(manager, app, "")
    ref_rt.start()
    _feed(ref_rt, events)
    assert ref, "the case must produce rows"

    a = ", async='true'" if mode == "async" else ""
    rt, got = _deploy(
        manager, app, f"@device(strict='true', batch='{batch}'{options}{a})")
    bridge, = rt.device_bridges
    r = bridge.runtime
    assert bridge.kind == BRIDGE_KIND.get(kind, kind)
    assert isinstance(r, StepRuntime)
    assert (bridge.driver is not None) == (mode == "async")
    cls = type(r)
    for name in ("process", "deliver", "collect"):
        assert getattr(cls, name) is getattr(StepRuntime, name), name
    if kind == "partition":     # its direct flush(decode=) over `builders`
        assert cls.flush is not StepRuntime.flush
    else:
        assert cls.flush is StepRuntime.flush
    assert not set(SHARED) & set(vars(cls)), set(SHARED) & set(vars(cls))

    seen = _tap_phases(r)
    rt.start()
    _feed(rt, events)
    rt.flush_device()

    assert seen and all(p is not None for p in seen)
    keys = {frozenset(p) for p in seen}
    assert len(keys) == 1, keys
    first_kind, first_keys = _PHASE_KEYS.setdefault(mode, (kind, keys))
    assert keys == first_keys, (kind, first_kind, keys ^ first_keys)
    assert {"fence_s", "decode_s", "step_s", "parts", "cause"} <= \
        set(seen[0])
    assert ("lock_s" in seen[0]) == (mode == "async")

    want, have = [row for _ts, row in ref], [row for _ts, row in got]
    if not ordered:
        want, have = sorted(want), sorted(have)
    assert len(want) == len(have)
    for w, h in zip(want, have):
        assert rows_equal(w, h), (w, h)


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_sync_flush_stamps_the_batchs_last_ts_and_drains_once(manager,
                                                                kind):
    """The sync ``flush`` is ``StepRuntime``'s for every kind: the chunk
    goes out with the batch's own ``last_ts`` (not the bridge's running
    ``_out_ts``), and ``on_drained`` follows it, once."""
    app, events, _batch, options, _ordered = KINDS[kind]
    # one batch that never fills: nothing is stepped before the flush
    rt, got = _deploy(manager, app,
                      f"@device(strict='true', batch='512'{options})")
    bridge, = rt.device_bridges
    r = bridge.runtime
    drained = []
    inner = r.on_drained

    def on_drained():
        drained.append(1)
        inner()

    r.on_drained = on_drained
    rt.start()
    _feed(rt, events)
    assert not got and not drained and len(r.builder) == len(events)
    bridge._out_ts = -1         # what deliver must not fall back on
    rt.flush_device()
    assert len(drained) == 1
    assert got and {ts for ts, _row in got} == {events[-1][2]}
    rt.flush_device()           # nothing staged: no step, no drain point
    assert len(drained) == 1


def _imported(path, package):
    """Absolute names of everything a module of ``package`` imports."""
    parts = package.split(".")
    for node in ast.walk(ast.parse(open(path).read(), path)):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = parts[:len(parts) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            yield mod
            yield from (f"{mod}.{a.name}" for a in node.names)


def test_the_device_package_stands_without_the_flow_package():
    """``siddhi_tpu/flow`` plugs its controller into a runtime
    (``batch_controller``); nothing under ``siddhi_tpu/tpu`` imports it."""
    root = os.path.join(REPO, "siddhi_tpu", "tpu")
    seen, offenders = set(), []
    for dirpath, _dirs, files in os.walk(root):
        rel = os.path.relpath(dirpath, os.path.join(REPO))
        package = rel.replace(os.sep, ".")
        for f in files:
            if f.endswith(".py"):
                names = set(_imported(os.path.join(dirpath, f), package))
                seen |= names
                if any(n == "siddhi_tpu.flow"
                       or n.startswith("siddhi_tpu.flow.") for n in names):
                    offenders.append(os.path.join(rel, f))
    # the walk does resolve relative imports: these two are there
    assert {"siddhi_tpu.observability.profiler.span",
            "siddhi_tpu.tpu.step_runtime.StepRuntime"} <= seen
    assert not offenders, offenders
