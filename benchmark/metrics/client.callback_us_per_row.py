"""Client (the benchmark's): time from one row reaching the benchmark's
callback to the next, within a burst (a gap of 1 ms or more starts a new
burst), mean over the window's rows. A burst is one call of the callback
with a batch's ``Event``s, built ahead of it, so the gap is the cost of the
benchmark's own loop over that list and no reading of the egress layer
(``egress.publish_ms_per_batch`` is)."""

import numpy as np


def read(run):
    stamps = run.window_row_stamps()
    if stamps.size < 2:
        return None
    gaps = np.diff(stamps)
    gaps = gaps[gaps < 1e-3]
    return float(gaps.mean()) * 1e6 if gaps.size else None
