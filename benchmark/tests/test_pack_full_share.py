"""``step.pack_full_share`` (PR 36), the reader of how often the scan NFA's
step took its whole pack, on hand-made counters as ``test_phase_readers.py``
holds every phase reader, and in the cell that reports it.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import pytest

import test_phase_readers
from harness import manifest

MANIFEST = test_phase_readers.MANIFEST
NAME = "step.pack_full_share"
BATCH = 2048


def _counters(decode_full_batches, batches=100):
    """The window's two edges with ``batches`` decodes in it, of them
    ``decode_full_batches`` of the whole table (None: a program without
    the tracker)."""
    at_open, at_close = test_phase_readers._phase_counters(
        {"egress_decode": 0.0008}, batches, batch=BATCH)
    if decode_full_batches is not None:
        at_open["phase.decode_full.count"] = 3 * BATCH
        at_close["phase.decode_full.count"] = \
            (3 + decode_full_batches) * BATCH
    return at_open, at_close


@pytest.mark.parametrize("full, batches, want", [
    (None, 100, None),      # no tracker (a program before PR 34): left out
    (0, 100, 0.0),          # the packed table always sufficed
    (7, 100, 7.0),          # seven batches of a hundred took the whole pack
    (100, 100, 100.0),      # every batch did: the count is paid twice
    (0, 0, None),           # nothing stepped in the window: no division
], ids=["no-tracker", "never", "some", "always", "idle"])
def test_the_share_of_batches_that_took_the_whole_pack(full, batches, want):
    read = manifest.metric_reader(NAME)
    got = read(test_phase_readers._run_with(*_counters(full, batches)))
    assert got == (None if want is None else pytest.approx(want))
    assert read(test_phase_readers._run_with({}, {})) is None


def test_the_scan_cell_reports_it_and_no_other_cell_does():
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter",
                     "layer": "jitted step, device",
                     "moves": "throughput_eps",
                     "workloads": ["partitioned-kleene-sat"]}
    for w in MANIFEST["workloads"]:
        names = [m["name"] for m in manifest.Cell(MANIFEST, w["name"]).per_layer]
        assert (NAME in names) == (w["name"] == "partitioned-kleene-sat")
