"""Generator: send time minus due time, 99th percentile over the window's
events (a block's events are taken as sent evenly between its two clock
reads). A starved generator must not read as a fast server."""

import numpy as np

from harness.stats import percentile


def read(run):
    b = run.window_blocks()
    if b is None or not run.rate:
        return None
    first, count, t0, t1 = b
    n = int(count.sum())
    if n == 0:
        return None
    block_of = np.repeat(np.arange(len(count)), count)
    within = np.arange(n) - np.repeat(np.cumsum(count) - count, count) + 1
    sent = t0[block_of] + within / count[block_of] * (t1 - t0)[block_of]
    due = run.t_start + (first[block_of] + within - 1) / run.rate
    return percentile((sent - due) * 1e3, 99)
