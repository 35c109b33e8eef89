"""Generator: share of the window spent inside sends beyond the median cost
of a send (per event, over the window's blocks): the time the client sat
blocked on the engine lock or on the driver's full ring."""

import numpy as np


def read(run):
    b = run.window_blocks()
    if b is None or not run.rate:
        return None
    _, count, t0, t1 = b
    if count.size == 0:
        return None
    per_event = (t1 - t0) / count
    excess = np.maximum(0.0, (t1 - t0) - np.median(per_event) * count)
    return float(excess.sum()) / (run.t_close - run.t_open) * 100.0
