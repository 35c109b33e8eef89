"""Tests of the benchmark's own arithmetic, data files and `correct`.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the repo's tier-1 tests (`tests/`): these guard the yardstick.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import checks, manifest, stats, tracing, traffic  # noqa: E402
from harness.peaks import peaks_for, roofline_share_pct  # noqa: E402

MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]


# ---------------------------------------------------------------------------
# arithmetic on hand-made samples
# ---------------------------------------------------------------------------

def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 99) == 99
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _steady_rows(rate=1000.0, seconds=10.0, stall=None):
    """One row per event, delivered 10 ms after it was due; with ``stall``
    = (from, to) nothing is delivered in that stretch and what was held
    back arrives at its end."""
    n = int(rate * seconds)
    due = np.arange(n) / rate
    stamp = due + 0.010
    if stall is not None:
        held = (stamp >= stall[0]) & (stamp < stall[1])
        stamp[held] = stall[1]
    return due, stamp, np.arange(n)


def test_throughput_counts_all_events_over_all_seconds():
    _, stamp, last = _steady_rows()
    assert stats.throughput_eps(stamp, last, 1.0, 9.0) == pytest.approx(
        1000.0, rel=1e-3)


def test_a_stall_lowers_the_rate_and_raises_the_tail():
    due, stamp, last = _steady_rows()
    due_s, stamp_s, _ = _steady_rows(stall=(8.5, 9.5))
    steady = stats.throughput_eps(stamp, last, 1.0, 9.0)
    stalled = stats.throughput_eps(stamp_s, last, 1.0, 9.0)
    assert stalled < steady * 0.95      # half a second of nine is missing
    keep = (due >= 1.0) & (due < 9.0)
    lat = stats.latencies_ms(stamp[keep], due[keep])
    lat_s = stats.latencies_ms(stamp_s[keep], due_s[keep])
    assert stats.percentile(lat, 95) == pytest.approx(10.0)
    assert stats.percentile(lat_s, 95) > 400.0
    assert stats.percentile(lat_s, 50) == pytest.approx(10.0)


def test_drift_is_flat_when_steady_and_positive_under_a_growing_backlog():
    due, stamp, _ = _steady_rows()
    keep = (due >= 1.0) & (due < 9.0)
    lat = stats.latencies_ms(stamp[keep], due[keep])
    assert stats.p50_drift_pct(lat, due[keep], 1.0, 9.0) == pytest.approx(0.0)
    growing = lat + (due[keep] - 1.0) * 5.0     # 5 ms more each second
    assert stats.p50_drift_pct(growing, due[keep], 1.0, 9.0) > 50.0


def test_spread_uses_the_drivers_quartiles():
    import statistics
    vals = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


def test_peaks_table_and_roofline():
    assert peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("some other chip")
    # 819 kB in 1 ms is 0.1 % of the bandwidth roofline
    assert roofline_share_pct({"bytes": 819e3, "flops": 0.0}, "TPU v5 lite",
                              1e-3) == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# the trace reduction on a recorded trace
# ---------------------------------------------------------------------------

def test_trace_reduction_on_a_hand_made_trace():
    ms = 1_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_step", 10 * ms, 4 * ms], ["jit_step", 30 * ms, 4 * ms]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10 * ms, 3 * ms], ["fusion.2", 13 * ms, 1 * ms],
                ["fusion.1", 30 * ms, 3 * ms], ["fusion.2", 33 * ms, 1 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "client", "events": [["bench:send", 0, 40 * ms]]},
            {"name": "driver", "events": [
                ["siddhi:collect:q", 14 * ms, 6 * ms]]}]}]}
    r = tracing.reduce(trace)
    assert r["busy_s"] == pytest.approx(0.008)
    assert r["window_s"] == pytest.approx(0.040)
    assert r["steps"] == 2
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]
    gaps = dict(r["idle_gaps"])
    # gaps: 0-10, 14-30, 34-40 ms; the driver's collect covers 14-20
    assert gaps["siddhi:collect:q"] == pytest.approx(0.006)
    assert gaps["bench:send"] == pytest.approx(0.026)
    assert gaps["longest_gap"] == pytest.approx(0.016)
    assert "unattributed" not in gaps


def test_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce({"planes": [{"name": "/host:CPU", "lines": [
            {"name": "t", "events": [["bench:send", 0, 10]]}]}]})


def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(BENCH_DIR, "tests", "data", "trace_small.json")
    with open(path, encoding="utf-8") as f:
        rec = json.load(f)
    r = tracing.reduce(rec["trace"])
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-6), key
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell_name", CELLS)
def test_generator_same_seed_same_events_other_seed_other_events(cell_name):
    cell = manifest.Cell(MANIFEST, cell_name)
    make = lambda seed: traffic.make_pool(  # noqa: E731
        cell.config, cell.config_name, cell.traffic, seed, 4096)
    a, b, c = make(2**31 + 5), make(2**31 + 5), make(2**31 + 6)
    assert list(a) == cell.config["stream"]["columns"]
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    first = traffic.expand(a, 6000)
    assert all(len(v) == 6000 for v in first.values())
    assert all(np.array_equal(v[4096:], a[k][:6000 - 4096])
               for k, v in first.items())


# ---------------------------------------------------------------------------
# the data files
# ---------------------------------------------------------------------------

def test_manifest_names_units_and_files():
    names = set()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[section]:
            assert manifest.NAME_RE.match(entry["name"]), entry["name"]
            assert entry["name"] not in names or section == "workloads"
            names.add(entry["name"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert callable(manifest.metric_reader(m["name"]))
    for w in MANIFEST["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert 1 <= MANIFEST["run_seconds"] <= 51


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_loads_and_reports_what_the_contract_asks(cell_name):
    cell = manifest.Cell(MANIFEST, cell_name)
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    assert "@device(" in cell.app_text and "@app:adaptive" not in cell.app_text
    assert cell.traffic["loop"] in ("closed", "open")
    if cell.traffic["loop"] == "open":
        assert cell.traffic["rate_eps"] > 0
    cfg_entry = next(c for c in MANIFEST["configs"]
                     if c["name"] == cell.config_name)
    assert sorted(cfg_entry["reduced"]) == sorted(cell.config["reduced"])
    work = cell.reference.least_work(cell.config)
    assert work["bytes"] > 0 and work["flops"] > 0


def test_every_file_of_configs_traffic_cells_and_metrics_is_used():
    used = {"configs": set(), "traffic": set(), "cells": set(),
            "metrics": {m["name"] for m in MANIFEST["per_layer"]}}
    for w in MANIFEST["workloads"]:
        used["configs"].add(w["config"])
        used["traffic"].add(w["traffic"])
        used["cells"].add(w["name"])
    for folder, names in used.items():
        for fname in os.listdir(os.path.join(BENCH_DIR, folder)):
            if fname.startswith("__"):
                continue
            stem = fname.rsplit(".", 1)[0]
            assert stem in names, f"{folder}/{fname} is named by nothing"


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def test_compare_rows_ordered_and_unordered():
    ref = {"columns": {"a": np.array([1, 2, 3]), "x": np.array([.5, 1.5, 2.5])},
           "last_event": np.array([4, 5, 9]), "ordered": True}
    same = {"a": np.array([1, 2, 3, 0]), "x": np.array([.5, 1.5, 2.5, 0.])}
    r = checks.compare_rows(ref, same, 3)
    assert (r["rows_missing"], r["rows_extra"], r["rows_wrong"]) == (0, 0, 0)
    assert r["map"].tolist() == [0, 1, 2]
    wrong = {"a": np.array([1, 2, 3]), "x": np.array([.5, 1.75, 2.5])}
    assert checks.compare_rows(ref, wrong, 3)["rows_wrong"] == 1
    assert checks.compare_rows(ref, same, 2)["rows_missing"] == 1
    assert checks.compare_rows(ref, same, 4)["rows_extra"] == 1
    ref["ordered"] = False
    shuffled = {"a": np.array([3, 1, 2]), "x": np.array([2.5, .5, 1.5])}
    r = checks.compare_rows(ref, shuffled, 3)
    assert (r["rows_missing"], r["rows_extra"]) == (0, 0)
    assert r["map"].tolist() == [2, 0, 1]
    r = checks.compare_rows(ref, wrong, 3)
    assert (r["rows_missing"], r["rows_extra"]) == (1, 1)


def _interpreter_rows(cell, stream: dict, n: int) -> dict:
    """The scalar interpreter (the app text without its @device line) on the
    same events: the second witness the plain reference is held against."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    text = "\n".join(line for line in cell.app_text.splitlines()
                     if not line.startswith("@device"))
    cfg = cell.config
    rows: list = []
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(text, playback=True)
        assert not rt.device_bridges and not rt.host_bridges
        rt.add_callback(cfg["output"]["stream"], StreamCallback(
            lambda evs: rows.extend(list(e.data) for e in evs)))
        rt.start()
        send = rt.input_handler(cfg["stream"]["id"]).send
        cols = [stream[k].tolist() for k in cfg["stream"]["columns"]]
        for i, row in enumerate(zip(*cols)):
            send(list(row), timestamp=cfg["base_timestamp"] + i)
    finally:
        m.shutdown()
    names = [c[0] for c in cfg["output"]["columns"]]
    return {k: np.array([r[j] for r in rows]) for j, k in enumerate(names)}, \
        len(rows)


@pytest.mark.parametrize("config_name", CONFIGS)
def test_plain_reference_agrees_with_the_scalar_interpreter(config_name):
    cell = manifest.Cell(MANIFEST, next(
        w["name"] for w in MANIFEST["workloads"]
        if w["config"] == config_name))
    n = 9000
    stream = traffic.make_pool(cell.config, config_name, cell.traffic, 11, n)
    ref = cell.reference.reference(cell.config, stream, n)
    assert len(ref["last_event"]) > 50
    got, n_got = _interpreter_rows(cell, stream, n)
    r = checks.compare_rows(ref, got, n_got)
    assert (r["rows_missing"], r["rows_extra"], r["rows_wrong"]) == (0, 0, 0)


@pytest.mark.parametrize("config_name", CONFIGS)
def test_the_control_comes_out_not_correct(config_name):
    """The reference in bfloat16 in the program's place has to fail the
    comparison (at a size a test run can hold)."""
    import ml_dtypes

    cell = manifest.Cell(MANIFEST, next(
        w["name"] for w in MANIFEST["workloads"]
        if w["config"] == config_name))
    n = 60_000
    for seed in (1, 2, 3):
        stream = traffic.make_pool(cell.config, config_name, cell.traffic,
                                   seed, n)
        ref = cell.reference.reference(cell.config, stream, n)
        low = cell.reference.reference(cell.config, stream, n,
                                       dtype=ml_dtypes.bfloat16)
        r = checks.compare_rows(ref, low["columns"], len(low["last_event"]))
        assert r["rows_missing"] + r["rows_extra"] + r["rows_wrong"] > 0


# ---------------------------------------------------------------------------
# the rest of a run with the timed path broken underneath
# ---------------------------------------------------------------------------

def _fault_state_unchanged(rt):
    """A step that returns its state unchanged."""
    import jax

    r = rt.device_bridges[0].runtime
    inner = r.dispatch

    def dispatch(batch):
        # the step donates its state's buffers: keep a copy to put back
        before = jax.tree_util.tree_map(lambda x: x.copy(), r.state)
        out = inner(batch)
        r.state = before
        return out

    r.dispatch = dispatch


def _fault_half_batch(rt):
    """Half of each batch left out."""
    r = rt.device_bridges[0].runtime
    inner = r.dispatch

    def dispatch(batch):
        b = dict(batch)
        keep = int(b["count"]) // 2
        valid = np.array(b["valid"], copy=True)
        valid[keep:] = False
        b["valid"], b["count"] = valid, keep
        return inner(b)

    r.dispatch = dispatch


def _fault_answer_altered(rt):
    """An answer altered where it is produced."""
    r = rt.device_bridges[0].runtime
    inner = r.collect

    def collect(token):
        rows = inner(token)
        if rows:
            rows[0] = list(rows[0])
            rows[0][-1] = rows[0][-1] + 1
        return rows

    r.collect = collect


def _rehearse(cell_name, after_deploy=None):
    from harness.runner import run_cell
    import time

    cell = manifest.Cell(MANIFEST, cell_name)
    result, rc = run_cell(cell, seed=5, seconds=1.5, trace=False,
                          t_process=time.perf_counter(), rehearsal=True,
                          after_deploy=after_deploy, say=lambda _m: None)
    assert rc == 0 and result is not None
    return result


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_sound_rehearsal_is_correct(cell_name):
    result = _rehearse(cell_name)
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch,
                                   _fault_answer_altered])
@pytest.mark.parametrize("config_name", CONFIGS)
def test_a_broken_timed_path_comes_out_not_correct(config_name, fault):
    cell_name = next(w["name"] for w in MANIFEST["workloads"]
                     if w["config"] == config_name)
    result = _rehearse(cell_name, after_deploy=fault)
    assert result["correct"] is False
    bad = {k: v for k, (v, lim) in result["compared"].items() if v > lim}
    assert bad, result["compared"]


def test_a_guard_replay_fails_the_run_although_rows_are_right():
    """A device step that raises once is replayed on the host by the
    DeviceGuard: the rows stay right and `correct` has to be false."""
    def raise_once(rt):
        r = rt.device_bridges[0].runtime
        inner, state = r.dispatch, {"left": 1}

        def dispatch(batch):
            if state["left"] and int(batch["count"]) > 0:
                state["left"] -= 1
                raise RuntimeError("sabotaged device step")
            return inner(batch)

        r.dispatch = dispatch

    result = _rehearse("window-groupby-sat", after_deploy=raise_once)
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["guard_failures"][0] + compared["warnings_logged"][0] \
        + compared["events_unaccounted"][0] > 0


def test_no_tpu_no_result(capsys):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True)
    assert proc.returncode != 0
    assert "cpu" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
