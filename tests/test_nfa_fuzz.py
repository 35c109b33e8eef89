"""Differential NFA fuzz: randomized pattern/sequence shapes × randomized
streams, host oracle vs the device NFA kernels.

Same rationale as ``test_device_fuzz.py`` for stream queries: the 126-case
corpus pins known reference behaviors; this sweep samples chain length ×
predicate thresholds × count states × ``every`` × ``within`` × batch size
on random data to hunt unknown divergences in the kernel the north-star
query rides. Fixed seeds — failures reproduce exactly."""

import random

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback

START = 1_000_000


def _chain(rng):
    """Random linear pattern over one or two streams."""
    n_states = rng.choice([2, 2, 3, 4])
    two_streams = rng.random() < 0.4
    streams = ("define stream A (k string, v long);\n"
               "define stream B (k string, v long);\n") if two_streams \
        else "define stream A (k string, v long);\n"
    parts = []
    for i in range(1, n_states + 1):
        sid = "A" if not two_streams or i % 2 else "B"
        if i == 1:
            pred = f"[v > {rng.randrange(20, 70)}]"
        else:
            pred = rng.choice([
                f"[v > e{i-1}.v]", f"[v < e{i-1}.v]",
                f"[v > {rng.randrange(10, 60)}]",
                f"[k == e1.k]",
            ])
        count = f"<{rng.choice([1, 2])}:{rng.choice([2, 3])}>" \
            if i < n_states and rng.random() < 0.25 else ""
        parts.append(f"e{i}={sid}{pred}{count}")
    joiner = ", " if rng.random() < 0.3 else " -> "
    body = joiner.join(parts)
    if rng.random() < 0.7:
        body = "every " + body
    within = f" within {rng.choice([300, 800, 2000])}" \
        if rng.random() < 0.5 else ""
    sel = ", ".join(f"e{i}.v as v{i}" for i in range(1, n_states + 1)
                    if "<" not in parts[i - 1] or True)
    return (streams + f"from {body}{within}\nselect {sel} "
            f"insert into OutputStream;\n", two_streams)


def _events(rng, n, two_streams):
    ts, out = START, []
    for _ in range(n):
        ts += rng.choice([20, 50, 50, 150, 600])
        sid = "B" if two_streams and rng.random() < 0.4 else "A"
        out.append((sid, [rng.choice("xy"), rng.randrange(100)], ts))
    return out


def _host(app, events):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True, start_time=START)
    rows = []
    rt.add_callback("OutputStream",
                    StreamCallback(lambda evs: rows.extend(
                        list(e.data) for e in evs)))
    rt.start()
    for sid, row, ts in events:
        rt.input_handler(sid).send(list(row), timestamp=ts)
    m.shutdown()
    return rows


def _device(app, events, cap):
    from siddhi_tpu.tpu.expr_compile import DeviceCompileError
    from siddhi_tpu.tpu.nfa import DeviceNFARuntime
    try:
        rt = DeviceNFARuntime(app, slot_capacity=64, batch_capacity=cap,
                              start_time=START)
    except DeviceCompileError:
        return None
    rows = []
    rt.add_callback(rows.extend)
    for sid, row, ts in events:
        rt.send(sid, list(row), ts)
    rt.flush()
    return rows


@pytest.mark.parametrize("seed", range(20))
def test_nfa_differential_fuzz(seed):
    rng = random.Random(7000 + seed)
    app, two = _chain(rng)
    events = _events(rng, rng.choice([30, 60]), two)
    actual = _device(app, events, cap=rng.choice([8, 16, 32]))
    if actual is None:
        pytest.skip(f"host-only shape: {app.splitlines()[-2]}")
    expected = _host(app, events)
    assert len(expected) == len(actual), \
        f"match count {len(expected)} != {len(actual)} for:\n{app}"
    assert sorted(map(tuple, expected)) == sorted(map(tuple, actual)), app


def test_nfa_fuzz_device_coverage_share():
    compiled = total = 0
    from siddhi_tpu.tpu.expr_compile import DeviceCompileError
    from siddhi_tpu.tpu.nfa import DeviceNFARuntime
    for seed in range(30):
        rng = random.Random(9000 + seed)
        app, _ = _chain(rng)
        total += 1
        try:
            DeviceNFARuntime(app, slot_capacity=8, batch_capacity=8,
                             start_time=START)
            compiled += 1
        except DeviceCompileError:
            pass
    assert compiled / total >= 0.6, f"device coverage {compiled}/{total}"
