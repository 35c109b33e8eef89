"""Egress: time from one row reaching the callback to the next, within a
burst (rows of one batch are published back to back; a gap of 1 ms or more
starts a new burst), mean over the window's rows."""

import numpy as np


def read(run):
    stamps = run.window_row_stamps()
    if stamps.size < 2:
        return None
    gaps = np.diff(stamps)
    gaps = gaps[gaps < 1e-3]
    return float(gaps.mean()) * 1e6 if gaps.size else None
