"""Tests of what PR 25 added to the yardstick: the readers of the program's
phase trackers on hand-made counters, and the trace reduction on nested spans.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import manifest, tracing  # noqa: E402

MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


# ---------------------------------------------------------------------------
# the trace reduction on nested spans
# ---------------------------------------------------------------------------

def test_a_dotted_child_span_is_charged_and_its_parent_keeps_its_self_time():
    """The program's spans nest: `siddhi:collect.decode:q` inside
    `siddhi:collect:q`, `siddhi:seal.pack:q` (client thread) inside
    `bench:send`. `.` sorts before `:`, so a gap goes to the child, the
    parent keeps what no child covers, and a `siddhi:` span on the client
    thread wins over `bench:send`."""
    ms = 1_000_000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 10 * ms, 4 * ms]]},
            {"name": "XLA Ops", "events": [["fusion.1", 10 * ms, 4 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "client", "events": [
                ["bench:send", 0, 40 * ms],
                ["siddhi:seal.pack:q", 2 * ms, 1 * ms]]},
            {"name": "driver", "events": [
                ["siddhi:collect:q", 14 * ms, 10 * ms],
                ["siddhi:collect.fence:q", 14 * ms, 1 * ms],
                ["siddhi:collect.decode:q", 15 * ms + 500_000, 8 * ms],
                ["siddhi:deliver:q", 24 * ms, 6 * ms],
                ["siddhi:deliver.lock:q", 24 * ms, 2 * ms],
                ["siddhi:deliver.publish:q", 26 * ms, 4 * ms]]}]}]}
    gaps = dict(tracing.reduce(trace)["idle_gaps"])
    # the device is idle 0-10 and 14-40 ms
    assert gaps["siddhi:seal.pack:q"] == pytest.approx(0.001)
    assert gaps["siddhi:collect.fence:q"] == pytest.approx(0.001)
    assert gaps["siddhi:collect.decode:q"] == pytest.approx(0.008)
    assert gaps["siddhi:collect:q"] == pytest.approx(0.001)    # self time
    assert gaps["siddhi:deliver.lock:q"] == pytest.approx(0.002)
    assert gaps["siddhi:deliver.publish:q"] == pytest.approx(0.004)
    assert "siddhi:deliver:q" not in gaps       # wholly covered
    assert gaps["bench:send"] == pytest.approx(0.009 + 0.010)
    assert "unattributed" not in gaps


# ---------------------------------------------------------------------------
# the readers of the program's phase trackers, on hand-made counters
# ---------------------------------------------------------------------------

def _run_with(at_open: dict, at_close: dict):
    from harness.runner import Run
    run = Run(manifest.Cell(MANIFEST, CELLS[0]), "TPU v5 lite")
    run.at_open, run.at_close = at_open, at_close
    return run


def _phase_counters(seconds_a_batch: dict, batches: int, batch: int = 2048,
                    base: float = 5.0) -> tuple:
    """Counters at the window's two edges as `runner._counters` reads them:
    event-weighted counts and sums, `batches` batches in the window."""
    at_open = {"probe.events": 10 * batch}
    at_close = {"probe.events": (10 + batches) * batch}
    for phase, s in seconds_a_batch.items():
        at_open[f"phase.{phase}.count"] = 10 * batch
        at_open[f"phase.{phase}.sum"] = base * batch
        at_close[f"phase.{phase}.count"] = (10 + batches) * batch
        at_close[f"phase.{phase}.sum"] = (base + s * batches) * batch
    return at_open, at_close


SPLIT_S = {"pack": 0.0002, "device_step": 0.006, "egress_fence": 0.004,
           "egress_decode": 0.0008, "lock_wait": 0.002,
           "sink_publish": 0.0004, "ring_wait": 0.0}
NEW_READERS = {
    "bridge.pack_ms_per_batch": 0.2,
    "step.dispatch_ms_per_batch": 6.0,
    "step.fence_wait_ms_per_batch": 4.0,
    "egress.decode_ms_per_batch": 0.8,
    "egress.lock_wait_ms_per_batch": 2.0,
    "egress.publish_ms_per_batch": 0.4,
    "bridge.ring_wait_ms": 0.0,
    "driver.seal_to_rows_ms": 10.8,
    "egress.deliver_ms": 2.4,
}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_a_phase_reader_gives_the_windows_mean_in_ms(name):
    read = manifest.metric_reader(name)
    run = _run_with(*_phase_counters(SPLIT_S, batches=100))
    assert read(run) == pytest.approx(NEW_READERS[name], abs=1e-9)
    # nothing stepped in the window: nothing to read, and no division
    idle = _run_with(*_phase_counters(SPLIT_S, batches=0))
    assert read(idle) is None
    # a program without the trackers this PR adds (the parent commit):
    # the carved metrics are left out, they do not raise
    old = {k: v for k, v in SPLIT_S.items()
           if k not in ("egress_decode", "lock_wait", "ring_wait")}
    parent = _run_with(*_phase_counters(old, batches=100))
    if name in ("bridge.pack_ms_per_batch", "step.dispatch_ms_per_batch"):
        assert read(parent) == pytest.approx(NEW_READERS[name])
    else:
        assert read(parent) is None
    assert read(_run_with({}, {})) is None


def test_ring_wait_is_spread_over_every_event_stepped():
    """One batch of 2,048 waited 0.2 s on a full ring, 99 did not: the
    tracker holds one batch's events, the metric divides by all of them."""
    at_open, at_close = _phase_counters(SPLIT_S, batches=100)
    at_close["phase.ring_wait.count"] += 2048
    at_close["phase.ring_wait.sum"] += 0.2 * 2048
    read = manifest.metric_reader("bridge.ring_wait_ms")
    assert read(_run_with(at_open, at_close)) == pytest.approx(2.0)
