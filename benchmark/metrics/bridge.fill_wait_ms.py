"""Bridge: mean time from an event's due time to the seal of its batch, over
the window's events. A batch seals inside the send of its last event, so the
seal is the clock read after that block of sends. Read only where every
flush in the window was a capacity flush (``probe.flush_causes``)."""

import numpy as np


def read(run):
    b = run.window_blocks(tail=True)
    if b is None or not run.rate:
        return None
    causes = run.delta_causes()
    if not causes or set(causes) != {"capacity"}:
        return None
    first, count, _, t1 = b
    cap = run.batch_capacity
    lo, hi = run.i_open, run.i_close
    # event that fills the batch of each window event, and the block it is in
    events = np.arange(lo, hi)
    filler = (events // cap + 1) * cap - 1
    block = np.searchsorted(first + count - 1, filler)
    if block.max() >= len(first):
        return None     # the last batch never sealed by capacity
    due = run.t_start + events / run.rate
    return float(np.mean(t1[block] - due)) * 1e3
