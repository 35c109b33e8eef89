"""A hopping window's runtime pipelines like the others. A step resolves at
most ``flush_cap`` boundaries (grouped) or B (ungrouped) and defers the
rest, which a drain steps out of the LIVE state: right only while that state
is the batch's own. So the host tells from a batch's own timestamps whether
its step may defer one (at most ceil(span / H) boundaries fall in the span
past the newest timestamp stepped before), and only such a batch, or the
first after deploy or restore, is serial: the driver dispatches nothing
behind it until it is collected (``tpu/runtime.py`` ``_hop_pipelined``,
``core/device_bridge.py`` ``AsyncDeviceDriver._next_action``)."""

import threading

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.device_bridge import AsyncDeviceDriver
from siddhi_tpu.tpu import DeviceStreamRuntime

DEFINE = "define stream Bid (auction long, bidder long, price long);\n"
QUERIES = {
    # NEXmark Query 5, the benchmark's `nexmark-q5`: the grouped flush
    "grouped": ("from Bid#window.hopping(1000, 200)\n"
                "select auction, count() as num\ngroup by auction\n"
                "order by num desc\nlimit 1\ninsert into HotItems;\n"),
    "ungrouped": ("from Bid#window.hopping(1000, 200)\n"
                  "select sum(price) as total, count() as num, "
                  "max(price) as hi\ninsert into HotItems;\n"),
}
DEVICE = "@device(strict='true', batch='{batch}', window='1280'{more})\n"


def _even(n=3000, seed=5):
    """A bid a tick: a batch of 64 spans 64 ms, under one hop of 200."""
    rng = np.random.default_rng(seed)
    auction = rng.zipf(1.7, n) % 7 + np.arange(n) // 500 * 3 + 2 ** 33
    return auction, rng.integers(0, 1000, n), \
        rng.integers(100, 10 ** 6, n), 1_000_000 + np.arange(n)


def _sparse(n=600, seed=7):
    """Gaps of up to several hops: a batch of 64 crosses dozens of
    boundaries, more than one step resolves, grouped or not."""
    rng = np.random.default_rng(seed)
    auction = rng.zipf(1.7, n) % 5 + 2 ** 33
    gaps = rng.choice([1, 50, 700, 1500], n)
    return auction, rng.integers(0, 1000, n), \
        rng.integers(100, 10 ** 6, n), 1_000_000 + np.cumsum(gaps)


STREAMS = {"even": _even, "sparse": _sparse}


def _run(app, stream):
    """The app's rows for the stream sent as columns in chunks of 100 that
    straddle batches; and of its device bridge, where it has one, the step
    gauges, the window's drops and the async driver's window."""
    auction, bidder, price, ts = stream
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(DEFINE + app, playback=True)
        got = []
        rt.add_callback("HotItems", StreamCallback(
            lambda evs: got.extend(e.data for e in evs)))
        rt.start()
        ih = rt.input_handler("Bid")
        for s in range(0, len(ts), 100):
            ih.send_columns({"auction": auction[s:s + 100],
                             "bidder": bidder[s:s + 100],
                             "price": price[s:s + 100]}, ts[s:s + 100])
        rt.flush_device()
        bridge = rt.device_bridges[0] if rt.device_bridges else None
        gauges = dict(bridge.runtime.step_gauges) if bridge else None
        drops = int(bridge.runtime.state["window_drops"]) if bridge else 0
        window = bridge.driver.window if bridge and bridge.driver else None
        return got, gauges, drops, window
    finally:
        m.shutdown()


@pytest.mark.parametrize("stream", sorted(STREAMS))
@pytest.mark.parametrize("query", sorted(QUERIES))
def test_async_and_sync_equal_the_interpreter(query, stream):
    data = STREAMS[stream]()
    expected, _, _, _ = _run(QUERIES[query], data)
    assert expected, "the case must emit rows"
    served = {}
    for mode, more in (("sync", ""), ("async", ", async='true'")):
        served[mode] = _run(
            DEVICE.format(batch=64, more=more) + QUERIES[query], data)
    for mode, (got, gauges, drops, window) in served.items():
        assert got == expected, (mode, len(got), len(expected))
        # the ungrouped kernel counts an event the slide pushes out in a
        # step that fired no boundary as a drop, needed or not (its rows are
        # right): the even stream slides out a 64-event batch's worth in
        # most steps, 1,208 events in all
        assert drops == 0 or (query, stream) == ("ungrouped", "even")
        assert window == (2 if mode == "async" else None)
        serial = gauges["hop_serial_batches"]
        if stream == "even":
            assert serial == 1, (mode, serial)      # the first batch only
        else:
            assert serial > 1, (mode, serial)


def _batch(rt, ts):
    for t in ts:
        rt.builder.append([2 ** 33 + t % 3, 1, 100], int(t))
    return rt.builder.emit()


def test_a_batch_is_serial_past_what_one_step_resolves():
    """hop 200, a batch of 8: ``flush_cap`` 2. After the first batch (serial:
    no newest before it) a span of 400 past the newest holds at most two
    boundaries and pipelines; 401 may hold three and is serial. A restore
    and a serial batch whose drain never ran leave the next one serial."""
    rt = DeviceStreamRuntime(DEFINE + QUERIES["grouped"], batch_capacity=8,
                             window_capacity=64)
    assert rt.compiled.flush_cap == 2

    def step(ts):
        b = _batch(rt, ts)
        rt.process(b)
        return bool(b.get("_serial"))

    assert step(range(1000, 1008))                  # newest 1007
    assert not step([1100, 1407])                   # span 400
    assert step([1500, 1808])                       # span 401
    assert not step([1810])
    rt.restore_state(rt.snapshot_state())
    assert step([1811])
    assert not step([1812])
    rt.restore_state(rt.snapshot_state())
    inner = rt.compiled.decode_outputs
    rt.compiled.decode_outputs = lambda out: 1 / 0
    with pytest.raises(ZeroDivisionError):
        step([1813])                                # serial, not drained
    rt.compiled.decode_outputs = inner
    assert step([1814])
    assert not step([1815])
    assert rt.step_gauges["hop_serial_batches"] == 5


def test_ungrouped_resolves_a_batch_worth_of_boundaries():
    rt = DeviceStreamRuntime(DEFINE + QUERIES["ungrouped"], batch_capacity=8,
                             window_capacity=64)
    b = _batch(rt, [1000])
    rt.process(b)
    assert b["_serial"]
    b = _batch(rt, [1000 + 8 * 200])                # eight boundaries: B
    rt.process(b)
    assert "_serial" not in b
    b = _batch(rt, [1000 + 17 * 200])               # nine
    rt.process(b)
    assert b["_serial"]


def test_a_runtime_without_hopping_never_marks_a_batch_serial():
    rt = DeviceStreamRuntime(
        DEFINE + "from Bid#window.length(4) select sum(price) as s "
        "insert into O;", batch_capacity=8, window_capacity=8)
    for start in (0, 10 ** 6, 10 ** 9):             # gaps of any size
        b = _batch(rt, range(start, start + 8))
        out = rt.dispatch(b)
        assert "_serial" not in b and "hop_serial" not in out
        rt.collect(out)
    assert "hop_serial_batches" not in rt.step_gauges


class _Ctx:
    root_lock = threading.RLock()


class _StubRuntime:
    """What the driver calls, logged: batch ``i`` is token ``i``."""
    query_name = "stub"
    batch_controller = None

    def __init__(self, serial):
        self.serial, self.log, self.delivered = serial, [], []
        self.builder = []

    def dispatch(self, batch):
        self.log.append(("dispatch", batch["i"]))
        if batch["i"] in self.serial:
            batch["_serial"] = True
        return batch["i"]

    def collect(self, token):
        self.log.append(("collect", token))
        return [token]

    def deliver(self, rows, emit_ts):
        self.delivered.extend(rows)

    def observe_step(self, *args, **kwargs):
        pass

    def step_phases(self, batch, **kwargs):
        return {}

    def on_drained(self):
        pass


def test_the_driver_dispatches_nothing_behind_a_serial_batch():
    rt = _StubRuntime(serial={1, 4})
    driver = AsyncDeviceDriver(rt, _Ctx(), depth=16, window=2)
    try:
        driver.pause()
        for i in range(6):
            driver.submit({"i": i, "count": 1})
        driver.resume()
        assert driver.quiesce(timeout=10.0)
    finally:
        driver.stop()
    d = lambda i: ("dispatch", i)       # noqa: E731
    c = lambda i: ("collect", i)        # noqa: E731
    # 0 pipelines with 1 behind it; 1 is serial: collected before 2 goes;
    # 3 is in flight when 4 (serial) is dispatched, and 5 waits for 4
    assert rt.log == [d(0), d(1), c(0), c(1), d(2), d(3), c(2), d(4),
                      c(3), c(4), d(5), c(5)]
    assert rt.delivered == list(range(6))
