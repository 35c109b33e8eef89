"""Plain reference of ``window-groupby``: the semantics written down, with
nothing of the program in it.

``from Bids[price > F]#window.length(L) select auction, sum(price), count()
group by auction``: events that pass the filter enter a window of the last L
of them; each such event emits its auction with the sum and the count of
that auction's prices among the window's events, itself included.
"""

from __future__ import annotations

import numpy as np


def reference(config: dict, cols: dict, n: int, dtype=np.float64) -> dict:
    """Rows for the stream's first ``n`` events. ``dtype`` is the precision
    prices are held and summed in (the control passes a lower one)."""
    length = int(config["window_length"])
    price = np.asarray(cols["price"][:n], dtype=np.float64).astype(dtype)
    passing = np.flatnonzero(price > np.asarray(config["price_floor"], dtype))
    auction = np.asarray(cols["auction"][:n])[passing].astype(np.int64)
    p = price[passing]
    m = len(passing)
    pos = np.arange(m, dtype=np.int64)
    order = np.argsort(auction, kind="stable")      # by auction, then time
    a_s, pos_s = auction[order], pos[order]
    # sums in float64 of the held values are exact for quarters; a lower
    # dtype rounds the running sum as it would be kept there
    csum = np.cumsum(p[order].astype(np.float64))
    span = m + length + 1
    key = a_s * span + pos_s
    lo = np.searchsorted(key, a_s * span + np.maximum(pos_s - (length - 1),
                                                     0))
    before = np.where(lo > 0, csum[np.maximum(lo - 1, 0)], 0.0)
    here = np.arange(m, dtype=np.int64)
    total = np.empty(m, dtype=np.float64)
    count = np.empty(m, dtype=np.int64)
    total[order] = (csum - before).astype(dtype).astype(np.float64)
    count[order] = here - lo + 1
    return {"columns": {"auction": auction, "total": total, "n": count},
            "last_event": passing, "ordered": True}


def least_work(config: dict) -> dict:
    """Least bytes and operations one batch needs by the query's semantics:
    events in (auction i32, price f32, timestamp i64), the window's events and
    the groups' running sum and count read and written once, rows out at most
    one per event (auction i32, total f32, n i64); per event a compare, an
    add for the entering price and a subtract for the leaving one, and two
    count updates."""
    batch, length, groups = (int(config[k]) for k in
                             ("batch", "window_length", "groups"))
    state = length * (4 + 4) + groups * (4 + 8)
    return {"bytes": batch * (4 + 4 + 8) + 2 * state + batch * (4 + 4 + 8),
            "flops": batch * 5, "bound": "bytes"}
