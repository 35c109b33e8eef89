"""Plain reference of ``partitioned-kleene``: the semantics written down, with
nothing of the program in it (its own copy: it imports no other reference).

``partition with (dev of S)`` round ``every e1=S[v > T] -> e2=S[v > e1.v]<3:>
-> e3=S[v < e1.v] within W``: every key matches its own pattern, apart from
every other key's events. Within one key, in arrival order: every event over
the threshold opens a partial match; a partial collects each later event of
its key whose value exceeds its first one (a closure, unbounded); once it
holds three, the first later event of its key under its first value closes
it and is the row's last column; an event equal to the first value does
neither; an event under it that comes before the third collected one is
skipped (`->` is followed-by); a partial whose first event is more than W
older than the event at hand is dropped. The row: the first value, the first
collected one, the LAST collected one before the closing event, the closing
one. Timestamps are global and one apart, so "W older" counts events of
every key.

Written as the semantics read (`_one_key_at_a_time`, kept as the second
witness of a test), then made fast: every open partial walks its key's
later events one step a round, all partials of all keys at once, and leaves
the walk when it closes or its key or its `within` ends.
"""

from __future__ import annotations

import numpy as np

MIN_COUNT = 3


def _one_key_at_a_time(vals: list, times: list, threshold: float,
                       within: int) -> list:
    """One key's events in arrival order -> ``(closing time, v1, first,
    peak, back)`` of every match, the slow way: each event is shown to
    every partial alive."""
    rows, alive = [], []        # a partial: [t1, v1, collected values]
    for x, t in zip(vals, times):
        keep = []
        for p in alive:
            if t - p[0] > within:
                continue                        # expired
            if x > p[1]:
                p[2].append(x)                  # collected
            elif x < p[1] and len(p[2]) >= MIN_COUNT:
                rows.append((t, p[1], p[2][0], p[2][-1], x))
                continue                        # closed
            keep.append(p)
        alive = keep
        if x > threshold:
            alive.append([t, x, []])
    return rows


def reference(config: dict, cols: dict, n: int, dtype=np.float64) -> dict:
    """Rows for the stream's first ``n`` events. ``dtype`` is the precision
    values are held and compared in (the control passes a lower one)."""
    within = int(config["within_ms"])
    # values rounded to `dtype`, then compared as float64: the same order
    v = np.asarray(cols["v"][:n], dtype=np.float64).astype(dtype)
    opens = np.asarray(v > np.asarray(config["first_threshold"], dtype))
    v = v.astype(np.float64)
    _, key = np.unique(np.asarray(cols["dev"][:n]).astype("U"),
                       return_inverse=True)
    # a key's events together, in arrival order: a partial at position p
    # walks p + 1, p + 2, .. while the key and its `within` last
    order = np.argsort(key, kind="stable")
    key, val, when = key[order], v[order], order.astype(np.int64)
    size = len(val)
    at = np.flatnonzero(opens[order])           # where each partial opened
    v1, t1, k1 = val[at], when[at], key[at]
    count = np.zeros(len(at), dtype=np.int64)
    first = np.zeros(len(at))
    peak = np.zeros(len(at))
    out = {name: [] for name in ("last", "v1", "first", "peak", "back")}
    pos = at
    while len(pos):
        pos = pos + 1
        live = pos < size
        live[live] &= (key[pos[live]] == k1[live]) \
            & (when[pos[live]] - t1[live] <= within)
        pos, v1, t1, k1, count, first, peak = (
            a[live] for a in (pos, v1, t1, k1, count, first, peak))
        x = val[pos]
        higher = x > v1
        first = np.where(higher & (count == 0), x, first)
        peak = np.where(higher, x, peak)
        count = count + higher
        closes = (x < v1) & (count >= MIN_COUNT)
        if closes.any():
            for name, a in (("last", when[pos]), ("v1", v1),
                            ("first", first), ("peak", peak), ("back", x)):
                out[name].append(a[closes])
            keep = ~closes
            pos, v1, t1, k1, count, first, peak = (
                a[keep] for a in (pos, v1, t1, k1, count, first, peak))
    got = {name: (np.concatenate(parts) if parts else np.zeros(0))
           for name, parts in out.items()}
    last = got.pop("last").astype(np.int64)
    by_close = np.argsort(last, kind="stable")  # emission: by closing event
    return {"columns": {name: a[by_close] for name, a in got.items()},
            "last_event": last[by_close], "ordered": False}


def least_work(config: dict) -> dict:
    """Least bytes and operations one batch needs by the query's semantics:
    events in (value f32 + key code i32 + timestamp i64), every lane's
    waiting table read and written once. One table waits: a partial sits in
    the closure's table from its first event to its last (the closing state
    reads the same slots), ``lanes x slots`` slots of the first value, the
    first and the last collected one (f32 each), the key's code i32, the
    first timestamp i64, the count i32 and two flags: 30 bytes. Each event
    is compared with each slot of its own lane twice (does it extend the
    closure, does it close it). Rows out are data-dependent and left out (a
    lower bound)."""
    batch, slots, lanes = (int(config[k]) for k in ("batch", "slots",
                                                    "lanes"))
    return {"bytes": batch * (4 + 4 + 8) + 2 * lanes * slots * 30,
            "flops": batch * slots * 2, "bound": "bytes"}
