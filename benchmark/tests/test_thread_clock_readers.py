"""The readers of the threads' CPU clocks (PR 37) on hand-made counters, as
``test_phase_readers.py`` holds every phase reader: a value from the
window's two edges, ``None`` in a program without the tracker (the parent
commit) and where nothing was stepped; and the cells that report them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os

import pytest

import test_phase_readers
from harness import manifest

MANIFEST = test_phase_readers.MANIFEST
BATCH = 2048
# seconds a batch in each tracker the readers read
CLOCKS_S = {"client_cpu": 0.0118, "client_cycle": 0.0163,
            "device_step_cpu": 0.0015, "route_cpu": 0.0097,
            "egress_fence_cpu": 0.0001, "egress_decode_cpu": 0.0011,
            "sink_publish_cpu": 0.0070, "publish_build": 0.0050,
            "driver_cpu": 0.0102}
# metric -> (tracker, the reading for CLOCKS_S)
READERS = {
    "ingress.client_cpu_us_per_event": ("client_cpu", 0.0118 / BATCH * 1e6),
    "step.dispatch_cpu_ms_per_batch": ("device_step_cpu", 1.5),
    "step.fence_cpu_ms_per_batch": ("egress_fence_cpu", 0.1),
    "egress.decode_cpu_ms_per_batch": ("egress_decode_cpu", 1.1),
    "egress.publish_cpu_ms_per_batch": ("sink_publish_cpu", 7.0),
    "egress.publish_build_ms_per_batch": ("publish_build", 5.0),
    "host.driver_cpu_ms_per_batch": ("driver_cpu", 10.2),
    "bridge.route_cpu_ms_per_batch": ("route_cpu", 9.7),
}
SAT_CELLS = [w["name"] for w in MANIFEST["workloads"]
             if w["traffic"] == "sat"]
ROUTED = ["partitioned-chain-sat", "partitioned-kleene-sat"]


def _run(trackers: dict, batches: int = 100):
    at_open, at_close = test_phase_readers._phase_counters(
        trackers, batches, batch=BATCH)
    at_open["probe.steps"], at_close["probe.steps"] = 10, 10 + batches
    return test_phase_readers._run_with(at_open, at_close)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_clock_reader_gives_the_windows_mean(name):
    tracker, want = READERS[name]
    read = manifest.metric_reader(name)
    assert read(_run(CLOCKS_S)) == pytest.approx(want, rel=1e-9)
    # a thread that never ran in the segment reads 0, which is a reading
    assert read(_run({**CLOCKS_S, tracker: 0.0})) == 0.0
    # nothing stepped in the window: nothing to read, and no division
    assert read(_run(CLOCKS_S, batches=0)) is None
    # a program without the tracker (the parent commit): left out, no raise
    assert read(_run({k: v for k, v in CLOCKS_S.items()
                      if k != tracker})) is None
    assert read(_run(test_phase_readers.SPLIT_S)) is None
    assert read(test_phase_readers._run_with({}, {})) is None


def test_the_clients_cpu_is_spread_over_the_mean_batchs_events():
    """Batches sealed short (a ``lane_full`` flush) hold fewer events: the
    tracker's mean is seconds a batch, the metric divides by the window's
    events over its steps."""
    at_open, at_close = test_phase_readers._phase_counters(
        {"client_cpu": 0.010}, 100, batch=BATCH)
    at_open["probe.steps"], at_close["probe.steps"] = 10, 210
    read = manifest.metric_reader("ingress.client_cpu_us_per_event")
    got = read(test_phase_readers._run_with(at_open, at_close))
    assert got == pytest.approx(0.010 / (BATCH / 2) * 1e6)


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_cells_that_report_a_clock_metric(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["unit"] == ("us" if name.endswith("per_event") else "ms")
    assert (entry["better"], entry["source"], entry["moves"]) \
        == ("lower", "program_span", "throughput_eps")
    assert os.path.exists(os.path.join(
        manifest.BENCH_DIR, "metrics", name + ".py"))
    want = ROUTED if name == "bridge.route_cpu_ms_per_batch" else SAT_CELLS
    assert entry.get("workloads", SAT_CELLS) == want
    for w in MANIFEST["workloads"]:
        names = [m["name"]
                 for m in manifest.Cell(MANIFEST, w["name"]).per_layer]
        assert (name in names) == (w["name"] in want), w["name"]


def test_each_cpu_metric_sits_beside_the_wall_metric_of_its_segment():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    for cpu, wall in [
            ("ingress.client_cpu_us_per_event", "ingress.send_us_per_event"),
            ("step.dispatch_cpu_ms_per_batch", "step.dispatch_ms_per_batch"),
            ("step.fence_cpu_ms_per_batch", "step.fence_wait_ms_per_batch"),
            ("egress.decode_cpu_ms_per_batch", "egress.decode_ms_per_batch"),
            ("egress.publish_cpu_ms_per_batch", "egress.publish_ms_per_batch"),
            ("egress.publish_build_ms_per_batch",
             "egress.publish_ms_per_batch"),
            ("bridge.route_cpu_ms_per_batch", "bridge.route_ms_per_batch")]:
        assert cpu in names and wall in names
        layer = {m["name"]: m["layer"] for m in MANIFEST["per_layer"]}
        assert layer[cpu] == layer[wall], (cpu, wall)
        if "workloads" in next(m for m in MANIFEST["per_layer"]
                               if m["name"] == wall):
            assert next(m for m in MANIFEST["per_layer"]
                        if m["name"] == cpu)["workloads"] == ROUTED
