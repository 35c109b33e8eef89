"""What configurations added after ``conftest.py`` was written bring beside
the tests that were there: their pool checksums, and a second witness of a
reference that was made fast.

``test_benchmark.py``'s ``test_the_four_cells_draw_the_events_they_drew_at_
the_parent`` looks ``POOL_CRC[config, seed]`` up when it runs, and
``conftest.py`` joins only ``data/pool_crc.json`` into that table. A later
configuration brings its checksums as a file of its own,
``data/pool_crc/<config>.json`` (``{seed: {column: crc32}}``, taken in the PR
that added it), and this module joins every such file into the table as it
is imported: no file that was there is edited.
"""

from __future__ import annotations

import glob
import json
import os

import test_benchmark

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "pool_crc")
LATER = {}
for _path in sorted(glob.glob(os.path.join(_DIR, "*.json"))):
    _name = os.path.basename(_path)[:-len(".json")]
    with open(_path, encoding="utf-8") as _f:
        LATER[_name] = json.load(_f)
    for _seed, _crcs in LATER[_name].items():
        test_benchmark.POOL_CRC.setdefault((_name, int(_seed)), _crcs)


def test_every_later_configuration_is_in_the_table_and_in_the_benchmark():
    configs = {c["name"] for c in test_benchmark.MANIFEST["configs"]}
    assert "partitioned-kleene" in LATER
    for name, by_seed in LATER.items():
        assert name in configs, f"data/pool_crc/{name}.json names no config"
        assert sorted(int(s) for s in by_seed) == [0, 1, 2**31 + 5]
        for seed, crcs in by_seed.items():
            assert test_benchmark.POOL_CRC[name, int(seed)] == crcs


def test_the_fast_kleene_reference_is_the_slow_one():
    """``partitioned-kleene.py`` walks every partial of every key at once;
    its own first draft shows each event to every partial alive, a key at
    a time. Same rows on a stream small enough for the draft."""
    import numpy as np
    from harness import manifest, traffic

    cell = manifest.Cell(test_benchmark.MANIFEST, "partitioned-kleene-sat",
                         small=True)
    ref, cfg, n = cell.reference, cell.config, 20_000
    stream = traffic.make_pool(cfg, cell.config_name, cell.traffic, 7, n)
    fast = ref.reference(cfg, stream, n)
    rows = []
    keys, vals = stream["dev"].tolist(), stream["v"].tolist()
    for key in sorted(set(keys)):
        times = [t for t, k in enumerate(keys) if k == key]
        rows += ref._one_key_at_a_time(
            [vals[t] for t in times], times, cfg["first_threshold"],
            int(cfg["within_ms"]))
    assert len(rows) == len(fast["last_event"]) > 500
    got = sorted(zip(fast["last_event"].tolist(),
                     *(fast["columns"][c].tolist()
                       for c in ("v1", "first", "peak", "back"))))
    assert got == sorted(rows)
