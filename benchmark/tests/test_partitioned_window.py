"""What ``partitioned-window`` (PR 39) brings beside the parametrised tests
of ``test_benchmark.py``, which hold its reference against the scalar
interpreter at the ``small`` size, its control and its planted faults as
they hold every configuration's: a second witness of the reference, and the
control read where the issue asks for it to read, as rows wrong.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

import test_benchmark
from harness import checks, manifest, traffic


def _small():
    return manifest.Cell(test_benchmark.MANIFEST, "partitioned-window-sat",
                         small=True)


def test_the_shifted_maxima_are_a_window_a_key_walked_one_event_at_a_time():
    """``reference`` sorts by key and takes nine shifted maxima; its slow
    twin keeps a list of readings a key. Same rows, over the pool's wrap
    (the stream repeats it, so a key's window spans the seam)."""
    cell = _small()
    ref, cfg = cell.reference, cell.config
    for seed, n in ((4, 40_000), (2**31 + 9, 70_000)):
        pool = traffic.make_pool(cfg, cell.config_name, cell.traffic, seed,
                                 int(cfg["pool_events"]))
        stream = traffic.expand(pool, n)
        fast = ref.reference(cfg, stream, n)
        slow = ref._one_event_at_a_time(cfg, stream, n)
        assert len(slow) == len(fast["last_event"]) > 200
        assert [r[0] for r in slow] == fast["last_event"].tolist()
        assert [r[1] for r in slow] == fast["columns"]["roomNo"].tolist()
        assert [r[2] for r in slow] == fast["columns"]["deviceID"].tolist()
        assert np.array_equal(np.array([r[3] for r in slow], np.float32),
                              fast["columns"]["maxTemp"])


def test_the_control_reads_rows_wrong():
    """Readings held in bfloat16 round 99.75-99.99 to 100, which passes
    ``having``: the true rows come out with another maximum (rows wrong)
    and rows the reference does not have come out beside them."""
    cell = _small()
    cfg, n = cell.config, cell.test_sizes["control_events"]
    for seed in (1, 2, 3):
        stream = traffic.make_pool(cfg, cell.config_name, cell.traffic,
                                   seed, n)
        ref = cell.reference.reference(cfg, stream, n)
        low = cell.reference.reference(cfg, stream, n,
                                       dtype=ml_dtypes.bfloat16)
        r = checks.compare_rows(ref, low["columns"], len(low["last_event"]))
        assert r["rows_wrong"] > 0.5 * len(ref["last_event"]) > 100, r
        assert r["rows_extra"] > 0 and r["rows_missing"] == 0


def test_least_work_counts_the_events_in_and_each_events_window():
    cell = manifest.Cell(test_benchmark.MANIFEST, "partitioned-window-sat")
    work = cell.reference.least_work(cell.config)
    assert work == {"bytes": 32768 * 24 + 32768 * 44, "flops": 32768 * 10,
                    "bound": "bytes"}
