"""Plain reference of the fixture ``pattern-chain8-x4``: the same query as
``pattern-chain8`` at other sizes, so that configuration's own reference."""

import os

from harness.manifest import BENCH_DIR, load_module

_chain8 = load_module(os.path.join(BENCH_DIR, "configs", "pattern-chain8.py"),
                      "bench_reference_pattern_chain8_for_x4")
reference, least_work = _chain8.reference, _chain8.least_work
