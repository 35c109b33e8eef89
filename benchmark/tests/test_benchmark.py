"""Tests of the benchmark's own arithmetic, data files and `correct`.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the repo's tier-1 tests (`tests/`): these guard the yardstick.
"""

from __future__ import annotations

import json
import os
import re
import sys
import zlib

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
# a second manifest the parametrised tests are pointed at: one configuration
# that carries a `small` block (tests/data/configs), one cell, no chip run
FIXTURE = manifest.load_manifest(
    os.path.join(BENCH_DIR, "tests", "data", "manifest.json"))
MANIFESTS = {"benchmark": MANIFEST, "fixture": FIXTURE}
ALL_CONFIGS = [pytest.param(m, c["name"], id=f"{which}:{c['name']}")
               for which, m in MANIFESTS.items() for c in m["configs"]]
ALL_CELLS = [pytest.param(m, w["name"], id=f"{which}:{w['name']}")
             for which, m in MANIFESTS.items() for w in m["workloads"]]


def _first_cell_name(man: dict, config_name: str) -> str:
    return next(w["name"] for w in man["workloads"]
                if w["config"] == config_name)


def _first_cell(man: dict, config_name: str, small: bool = True):
    """The first cell of a configuration, at the sizes a CPU test can run."""
    return manifest.Cell(man, _first_cell_name(man, config_name), small=small)


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

@pytest.mark.parametrize("man, cell_name", ALL_CELLS)
def test_generator_same_seed_same_events_other_seed_other_events(man,
                                                                 cell_name):
    cell = manifest.Cell(man, cell_name)
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


def _crc(col: np.ndarray) -> int:
    if col.dtype == object:
        return zlib.crc32("\n".join(col.tolist()).encode())
    return zlib.crc32(np.ascontiguousarray(col).tobytes())


# crc32 of the first 65,536 events of each column of the run's own pool
# (`pool_events` drawn), taken on the parent commit 36e6d38 (PR 26) before
# `zipf` learnt `prefix` and `shift_every`: the four cells' events are theirs
POOL_CRC = {
    ("pattern-chain8", 0): {"dev": 2086414828, "v": 3668015034},
    ("pattern-chain8", 1): {"dev": 219436068, "v": 3922510504},
    ("pattern-chain8", 2**31 + 5): {"dev": 2225868474, "v": 1674609362},
    ("window-groupby", 0): {"auction": 1713293762, "bidder": 1809506076,
                            "price": 1061921721},
    ("window-groupby", 1): {"auction": 4106376094, "bidder": 3258360035,
                            "price": 4130364795},
    ("window-groupby", 2**31 + 5): {"auction": 3302533166,
                                    "bidder": 4251793665,
                                    "price": 716056508},
}


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
@pytest.mark.parametrize("cell_name", CELLS)
def test_the_four_cells_draw_the_events_they_drew_at_the_parent(cell_name,
                                                                 seed):
    cell = manifest.Cell(MANIFEST, cell_name)
    pool = traffic.make_pool(cell.config, cell.config_name, cell.traffic,
                             seed, int(cell.config["pool_events"]))
    assert {k: _crc(v[:65536]) for k, v in pool.items()} \
        == POOL_CRC[cell.config_name, seed]


ZIPF = {"dist": "zipf", "n_keys": 1024, "a": 0.6, "dtype": "int32"}


def test_a_plain_zipf_column_uses_the_random_stream_as_at_the_parent():
    """Checksums of the parent commit: the column itself and the one drawn
    after it from the same generator."""
    rng = traffic.seed_rng(2**31 + 5)
    col = traffic.draw_column(ZIPF, 65536, rng)
    after = traffic.draw_column({"dist": "uniform", "low": 0.0, "high": 100.0,
                                 "decimals": 3, "dtype": "float64"}, 65536,
                                rng)
    assert col.dtype == np.int32
    assert (_crc(col), _crc(after)) == (1466191000, 2372988833)


def test_a_zipf_hot_set_moves_and_keeps_its_rank_counts():
    n, every, by = 65536, 16384, 100
    plain = traffic.draw_column(ZIPF, n, traffic.seed_rng(7))
    spec = dict(ZIPF, shift_every=every, shift_by=by)
    moved = traffic.draw_column(spec, n, traffic.seed_rng(7))
    hottest = []
    for k in range(n // every):
        a, b = (c[k * every:(k + 1) * every] for c in (plain, moved))
        # the same ranks, each under the key `shift_by * k` further on
        assert np.array_equal(b, (a + by * k) % ZIPF["n_keys"])
        counts = np.bincount(b, minlength=ZIPF["n_keys"])
        assert np.array_equal(np.roll(counts, -by * k),
                              np.bincount(a, minlength=ZIPF["n_keys"]))
        hottest.append(int(counts.argmax()))
    assert hottest == [0, by, 2 * by, 3 * by]
    # the random stream is used as without the keys
    rng_a, rng_b = traffic.seed_rng(7), traffic.seed_rng(7)
    traffic.draw_column(ZIPF, n, rng_a)
    traffic.draw_column(dict(spec, prefix="dev"), n, rng_b)
    assert rng_a.integers(1 << 30) == rng_b.integers(1 << 30)


def test_a_zipf_column_with_a_prefix_is_labels():
    keys = traffic.draw_column(ZIPF, 4096, traffic.seed_rng(3))
    labels = traffic.draw_column(dict(ZIPF, prefix="dev"), 4096,
                                 traffic.seed_rng(3))
    assert labels.dtype == object
    assert labels.tolist() == [f"dev{k}" for k in keys.tolist()]


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


@pytest.mark.parametrize("man, cell_name", ALL_CELLS)
def test_every_cell_loads_and_reports_what_the_contract_asks(man, cell_name):
    cell = manifest.Cell(man, cell_name)
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    assert "@device(" in cell.app_text and "@app:adaptive" not in cell.app_text
    assert cell.traffic["loop"] in ("closed", "open")
    if cell.traffic["loop"] == "open":
        assert cell.traffic["rate_eps"] > 0
    cfg_entry = next(c for c in man["configs"]
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
        _files_are_named(os.path.join(BENCH_DIR, folder), names)
    _files_are_named(os.path.join(BENCH_DIR, "tests", "data", "configs"),
                     {w["config"] for w in FIXTURE["workloads"]})


def _files_are_named(folder: str, names: set) -> None:
    """Every file of ``folder`` is ``<a name of names>.<ending>``; a second
    app text ``<name>.small.siddhi`` is the one its configuration's ``small``
    block names."""
    for fname in os.listdir(folder):
        if fname.startswith("__"):
            continue
        stem = fname.rsplit(".", 1)[0]
        if fname.endswith(".small.siddhi"):
            stem = fname[:-len(".small.siddhi")]
            with open(os.path.join(folder, stem + ".json"),
                      encoding="utf-8") as f:
                assert json.load(f)["small"]["app"] == fname
        assert stem in names, f"{folder}/{fname} is named by nothing"


# ---------------------------------------------------------------------------
# the `small` block: sizes for these tests and a rehearsal, from the
# configuration
# ---------------------------------------------------------------------------

def test_the_small_block_is_read_by_the_tests_and_a_rehearsal_only():
    from harness import runner

    full = manifest.Cell(FIXTURE, "pattern-chain8-x4-sat")
    small = full.shrunk()
    assert small.small and small.shrunk() is small
    assert "small" not in full.config and "small" not in small.config
    assert [full.config[k] for k in ("within_ms", "batch", "slots")] \
        == [16000, 8192, 4096]
    assert [small.config[k] for k in ("within_ms", "batch", "slots")] \
        == [4000, 2048, 1024]
    assert "batch='8192'" in full.app_text and "within 16000" in full.app_text
    assert "batch='2048'" in small.app_text and "within 4000" in small.app_text
    assert full.test_sizes == manifest.Cell.TEST_SIZES
    assert small.test_sizes == {"interpreter_events": 7000,
                                "interpreter_rows_min": 30,
                                "control_events": 40_000}
    assert runner.plan(full, 30.0, False).batch == 8192
    assert runner.plan(small, 1.5, True).batch == 2048
    # a configuration without the block: today's numbers, the app text as is
    plain = manifest.Cell(MANIFEST, CELLS[0])
    assert plain.shrunk().config == plain.config
    assert plain.shrunk().app_text == plain.app_text
    assert plain.shrunk().test_sizes == manifest.Cell.TEST_SIZES


def test_a_timed_set_up_reads_nothing_of_a_small_block(tmp_path):
    """A `small` block that would change every number, planted into each
    configuration of BENCHMARK.json: a cell as a timed run builds it, and
    the sizes its set-up takes from it, are what they are without."""
    from harness import runner

    planted = json.loads(json.dumps(MANIFEST))
    for entry in planted["configs"]:
        src = os.path.join(ROOT, entry["file"])
        stem = src[:-len(".json")]
        with open(src, encoding="utf-8") as f:
            cfg = json.load(f)
        numbers = {k: v * 2 + 1 for k, v in cfg.items()
                   if isinstance(v, (int, float)) and k != "pool_events"}
        cfg["small"] = {
            "config": dict(numbers, pool_events=cfg["pool_events"] // 2,
                           columns={}, ingress="neither"),
            "app": entry["name"] + ".small.siddhi", "interpreter_events": 1,
            "interpreter_rows_min": 10**9, "control_events": 1}
        entry["file"] = str(tmp_path / (entry["name"] + ".json"))
        with open(entry["file"], "w", encoding="utf-8") as f:
            json.dump(cfg, f)
        (tmp_path / cfg["small"]["app"]).write_text("no app at all")
        for ending in (".siddhi", ".py"):
            with open(stem + ending, encoding="utf-8") as f:
                (tmp_path / (entry["name"] + ending)).write_text(f.read())
    for name in CELLS:
        clean, cell = manifest.Cell(MANIFEST, name), \
            manifest.Cell(planted, name)
        assert cell.config == clean.config
        assert cell.app_text == clean.app_text
        assert cell.test_sizes == clean.test_sizes
        assert cell.traffic == clean.traffic
        assert runner.plan(cell, 30.0, False) == runner.plan(clean, 30.0,
                                                             False)
        # and the block was there to read
        shrunk = cell.shrunk()
        assert shrunk.app_text == "no app at all"
        assert shrunk.config["batch"] == clean.config["batch"] * 2 + 1
        assert shrunk.test_sizes["control_events"] == 1


def _sizes_blanked(app_text: str) -> str:
    """An app text with the digits inside ``@device(...)`` and after
    ``within`` taken out."""
    text = re.sub(r"@device\([^)]*\)",
                  lambda m: re.sub(r"\d+", "#", m.group(0)), app_text)
    return re.sub(r"\bwithin\s+\d+", "within #", text)


@pytest.mark.parametrize("man, config_name", ALL_CONFIGS)
def test_the_two_app_texts_of_a_configuration_are_one_query(man, config_name):
    full = _first_cell(man, config_name, small=False)
    small = full.shrunk()
    assert _sizes_blanked(small.app_text) == _sizes_blanked(full.app_text)
    # the blanking leaves the query itself to compare
    assert _sizes_blanked("@device(batch='8') from S[v > 90.0] within 40") \
        == "@device(batch='#') from S[v > 90.0] within #"
    # and each text deploys the engine sizes its configuration states
    for cell in (full, small):
        assert f"batch='{cell.config['batch']}'" in cell.app_text
        for key, said in (("slots", "slots='{}'"), ("within_ms", "within {}")):
            if key in cell.config:
                assert said.format(cell.config[key]) in cell.app_text


def test_overflow_counters_are_summed_whatever_their_shape():
    import jax.numpy as jnp

    state = {"drops": jnp.zeros((), jnp.int32),
             "window_drops": jnp.zeros(4, jnp.int32), "other": jnp.ones(3)}
    assert checks.overflow_count(state) == 0
    state["drops"] = jnp.asarray(2, jnp.int32)
    assert checks.overflow_count(state) == 2
    # lane-stacked: one lane of four went over, three times
    state["window_drops"] = jnp.asarray([0, 0, 3, 0], jnp.int32)
    assert checks.overflow_count(state) == 5
    state["drops"] = np.zeros(4, np.int64)
    assert checks.overflow_count(state) == 3


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
    # unordered, what is left over on both sides pairs off as rows wrong
    r = checks.compare_rows(ref, wrong, 3)
    assert (r["rows_missing"], r["rows_extra"], r["rows_wrong"]) == (0, 0, 1)
    assert r["map"].tolist() == [0, -1, 2]
    more = {"a": np.array([1, 2, 3, 9]), "x": np.array([.5, 1.75, 2.5, 9.])}
    r = checks.compare_rows(ref, more, 4)
    assert (r["rows_missing"], r["rows_extra"], r["rows_wrong"]) == (0, 1, 1)
    r = checks.compare_rows(ref, wrong, 1)
    assert (r["rows_missing"], r["rows_extra"], r["rows_wrong"]) == (2, 0, 0)


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


@pytest.mark.parametrize("man, config_name", ALL_CONFIGS)
def test_plain_reference_agrees_with_the_scalar_interpreter(man, config_name):
    cell = _first_cell(man, config_name)
    n = cell.test_sizes["interpreter_events"]
    stream = traffic.make_pool(cell.config, config_name, cell.traffic, 11, n)
    ref = cell.reference.reference(cell.config, stream, n)
    assert len(ref["last_event"]) > cell.test_sizes["interpreter_rows_min"]
    got, n_got = _interpreter_rows(cell, stream, n)
    r = checks.compare_rows(ref, got, n_got)
    assert (r["rows_missing"], r["rows_extra"], r["rows_wrong"]) == (0, 0, 0)


@pytest.mark.parametrize("man, config_name", ALL_CONFIGS)
def test_the_control_comes_out_not_correct(man, config_name):
    """The reference in bfloat16 in the program's place has to fail the
    comparison (at a size a test run can hold)."""
    import ml_dtypes

    cell = _first_cell(man, config_name)
    n = cell.test_sizes["control_events"]
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
    """An answer altered where it is produced: one cell of each chunk the
    runtime's ``collect`` returns, in place (both egress shapes deliver the
    chunk's ``decoded()`` columns)."""
    r = rt.device_bridges[0].runtime
    inner = r.collect

    def collect(token):
        out = inner(token)
        if out:
            name = out.specs[-1][0]
            out.decoded()[name][0] += 1
        return out

    r.collect = collect


def _rehearse(man, cell_name, after_deploy=None):
    from harness.runner import run_cell
    import time

    cell = manifest.Cell(man, cell_name)
    result, rc = run_cell(cell, seed=5, seconds=1.5, trace=False,
                          t_process=time.perf_counter(), rehearsal=True,
                          after_deploy=after_deploy, say=lambda _m: None)
    assert rc == 0 and result is not None
    return result


@pytest.mark.parametrize("man, cell_name", ALL_CELLS)
def test_a_sound_rehearsal_is_correct(man, cell_name):
    result = _rehearse(man, cell_name)
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch,
                                   _fault_answer_altered])
@pytest.mark.parametrize("man, config_name", ALL_CONFIGS)
def test_a_broken_timed_path_comes_out_not_correct(man, config_name, fault):
    result = _rehearse(man, _first_cell_name(man, config_name),
                       after_deploy=fault)
    assert result["correct"] is False
    bad = {k: v for k, (v, lim) in result["compared"].items() if v > lim}
    assert bad, result["compared"]
    if fault is _fault_answer_altered:
        # one wrong answer is a row wrong and nothing else: no row missing,
        # no exception the guard swallowed and logged (`warnings_logged`)
        assert set(bad) == {"rows_wrong"}, bad


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

    result = _rehearse(MANIFEST, "window-groupby-sat", after_deploy=raise_once)
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
