"""Partitioned-NFA differential fuzz — the north star's operating shape:
``partition with (key of S)`` over a single-stream pattern, host oracle vs
``PartitionedNFARuntime`` (crc32 lanes → vmapped blocked/scan kernels).

``chip_smoke.py`` S3 checks ONE workload's rows; this sweep samples chain
length × predicates × every × within × key cardinality × lane counts ×
batch sizes and compares full match ROWS."""

import random

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu.expr_compile import DeviceCompileError
from siddhi_tpu.tpu.partition import PartitionedNFARuntime

START = 1_000_000


def _shape(rng):
    n_states = rng.choice([2, 3, 3, 4])
    parts = []
    for i in range(1, n_states + 1):
        if i == 1:
            pred = f"[v > {rng.randrange(40, 80)}]"
        else:
            pred = rng.choice([
                f"[v > e{i-1}.v]", f"[v < e{i-1}.v]",
                f"[v > {rng.randrange(10, 50)}]",
            ])
        parts.append(f"e{i}=S{pred}")
    body = " -> ".join(parts)
    if rng.random() < 0.8:
        body = "every " + body
    within = f" within {rng.choice([500, 1500, 4000])}" \
        if rng.random() < 0.6 else ""
    sel = ", ".join(f"e{i}.v as v{i}" for i in range(1, n_states + 1))
    return f"""
define stream S (dev string, v long);
partition with (dev of S)
begin
from {body}{within}
select {sel} insert into Alerts;
end;
"""


def _events(rng, n, n_keys):
    ts, out = START, []
    for _ in range(n):
        ts += rng.choice([20, 50, 50, 400])
        out.append(([f"d{rng.randrange(n_keys)}", rng.randrange(100)], ts))
    return out


def _host(app, events):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app, playback=True, start_time=START)
    rows = []
    rt.add_callback("Alerts", StreamCallback(
        lambda evs: rows.extend(list(e.data) for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    for row, ts in events:
        ih.send(list(row), timestamp=ts)
    m.shutdown()
    return rows


def _device(app, events, lanes, lane_batch):
    rt = PartitionedNFARuntime(
        app, num_partitions=lanes, key_attr="dev", slot_capacity=32,
        lane_batch=lane_batch, mesh=None)
    rows = []
    rt.callback = rows.extend
    for row, ts in events:
        rt.send("S", list(row), ts)
    rt.flush(decode=True)
    assert rt.drop_count == 0, "slot overflow invalidates parity"
    return rows


@pytest.mark.parametrize("seed", range(18))
def test_partitioned_nfa_differential_fuzz(seed):
    rng = random.Random(8000 + seed)
    app = _shape(rng)
    events = _events(rng, rng.choice([60, 120]),
                     n_keys=rng.choice([2, 5, 9]))
    lanes = rng.choice([2, 4, 8])
    lane_batch = rng.choice([16, 32])
    try:
        actual = _device(app, events, lanes, lane_batch)
    except DeviceCompileError:
        pytest.skip(f"host-only shape:\n{app}")
    expected = _host(app, events)
    # lanes emit independently: compare as multisets of match rows
    assert sorted(map(tuple, expected)) == sorted(map(tuple, actual)), app
