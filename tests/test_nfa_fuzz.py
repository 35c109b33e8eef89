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


# ---------------------------------------------------------------------------
# the scan kernel's row table never overflows where a partial emits once
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_a_batch_that_closes_every_partial_fits_the_row_table(seed):
    """``every A -> B<m:> -> C`` with its table at the brim when a batch
    begins; the batch closes EVERY partial at once, then seeds, collects
    and closes again, round after round: a partial emits once, so the rows
    of a batch are at most the C alive when it began plus one an event,
    ``C + B``: the rows of ``full``, which such a batch (more rows than its
    ``M = B`` events) is read from. Nothing is counted into ``drops`` and
    the rows are the host's."""
    from siddhi_tpu.tpu.nfa import DeviceNFARuntime
    rng = random.Random(4000 + seed)
    C, B = 16, 64
    m = rng.choice([1, 2, 3])
    app = ("define stream A (k string, v long);\n"
           f"from every e1=A[k == 'x' and v > 50] -> "
           f"e2=A[k == 'y' and v > e1.v]<{m}:> -> e3=A[k == 'z']\n"
           "select e1.v as v1, e2[0].v as f, e2[last].v as l, e3.v as z "
           "insert into OutputStream;\n")
    ts, events = START, []

    def send(k, v):
        nonlocal ts
        ts += 1
        events.append(("A", [k, v], ts))

    def seed_and_collect(n):
        for _ in range(n):
            send("x", rng.randrange(51, 90))
        for i in range(m):
            send("y", 95 + i)

    seed_and_collect(C)             # earlier batches: the table at its brim
    while len(events) % B:
        send("w", 0)                # neither opens, collects nor closes
    first = len(events)
    closed = C
    send("z", 1)                    # the batch: every partial closes..
    while len(events) - first < B - (m + 2):
        n = rng.randrange(1, min(C, B - (len(events) - first) - m - 1) + 1)
        seed_and_collect(n)         # ..and as many again as fit, each round
        send("z", 2)
        closed += n
    while len(events) % B:
        send("w", 0)
    assert len(events) == first + B and closed > C + B // 2
    rt = DeviceNFARuntime(app, slot_capacity=C, batch_capacity=B,
                          start_time=START)
    assert rt.compiler.M == B and rt.compiler._row_capacity() == C + B
    rows = []
    rt.add_callback(rows.extend)
    for sid, row, t in events:
        rt.send(sid, list(row), t)
    rt.flush()
    assert rt.drop_count == 0
    expected = _host(app, events)
    assert len(expected) == closed <= C + B
    assert sorted(map(tuple, expected)) == sorted(map(tuple, rows))
