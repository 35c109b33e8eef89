"""What `test_benchmark.py` cannot hold of a configuration added after it
was written, added beside it.

`test_the_four_cells_draw_the_events_they_drew_at_the_parent` looks each
configuration's checksums up in the table `POOL_CRC` in its own text. For a
configuration that came later the test means "its events stay what they
were when it was added": those checksums live in `data/pool_crc.json`
(`{config: {seed: {column: crc32}}}`, taken in the PR that added the
configuration) and join the table at collection. The next `benchmark` PR
folds the table into the data file and reads it in the test itself.
"""

from __future__ import annotations

import json
import os

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "pool_crc.json")


def pytest_collection_modifyitems(config, items):
    with open(_DATA, encoding="utf-8") as f:
        later = json.load(f)
    for module in {item.module for item in items
                   if hasattr(item.module, "POOL_CRC")}:
        for config_name, by_seed in later.items():
            for seed, crcs in by_seed.items():
                module.POOL_CRC.setdefault((config_name, int(seed)), crcs)
