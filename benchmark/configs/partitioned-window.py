"""Plain reference of ``partitioned-window``: the semantics written down, with
nothing of the program in it (its own copy: it imports no other reference).

``partition with (deviceID of TempStream)`` round ``from
TempStream#window.length(W) select roomNo, deviceID, max(temp) as maxTemp
having maxTemp > T``: every key has its own window of its last W events.
An event emits one row, its own ``roomNo`` and ``deviceID`` beside the
maximum of its key's last W readings up to and including its own, where
that maximum passes ``T``.

NumPy, no kernel, batching or key directory: the events sorted by key
(stably, so a key's events keep their order), each event's maximum taken
over itself and its up to W-1 same-key predecessors as W-1 shifted, masked
maxima over the sorted order, then put back in event order. Readings are
held in float32 as the device holds them (three decimals: every reading
distinct and ordered there), and ``having`` compares in float32 as the
device does with its constant.
"""

from __future__ import annotations

import numpy as np


def reference(config: dict, cols: dict, n: int, dtype=np.float32) -> dict:
    """Rows for the stream's first ``n`` events. ``dtype`` is the precision
    readings are held in (the control passes a lower one)."""
    length = int(config["window_length"])
    dev = np.asarray(cols["deviceID"][:n]).astype(np.int64)
    temp = np.asarray(cols["temp"][:n]).astype(np.float32).astype(dtype)
    order = np.argsort(dev, kind="stable")
    key, sv = dev[order], temp[order]
    at = np.arange(n)
    new = np.ones(n, dtype=bool)
    new[1:] = key[1:] != key[:-1]
    rank = at - np.maximum.accumulate(np.where(new, at, 0))
    best = sv.copy()
    for d in range(1, length):
        # the reading d events back in the key's own order, where it is one
        np.maximum(best[d:], sv[:-d], out=best[d:], where=rank[d:] >= d)
    peak = np.empty_like(best)
    peak[order] = best
    keep = np.flatnonzero(peak.astype(np.float32)
                          > np.float32(config["having_above"]))
    room = np.asarray(cols["roomNo"][:n]).astype(np.int64)
    return {"columns": {"roomNo": room[keep], "deviceID": dev[keep],
                        "maxTemp": peak[keep]},
            "last_event": keep.astype(np.int64), "ordered": False}


def _one_event_at_a_time(config: dict, cols: dict, n: int) -> list:
    """The same rows the slow way, a key's readings in a list each: the
    second witness of ``reference`` in ``benchmark/tests``."""
    length, above = int(config["window_length"]), float(config["having_above"])
    held: dict = {}
    rows = []
    for i, (d, r, t) in enumerate(zip(cols["deviceID"][:n].tolist(),
                                      cols["roomNo"][:n].tolist(),
                                      np.asarray(cols["temp"][:n])
                                      .astype(np.float32).tolist())):
        window = held.setdefault(d, [])
        window.append(t)
        del window[:-length]
        peak = max(window)
        if np.float32(peak) > np.float32(above):
            rows.append((i, r, d, peak))
    return rows


def least_work(config: dict) -> dict:
    """Least bytes and operations one batch needs by the query's semantics:
    the events in (deviceID i64 + roomNo i32 + temp f32 + timestamp i64),
    each event's key window read once (W readings f32) and its newest
    reading written once (f32); W compares an event. Rows out are
    data-dependent and left out (a lower bound)."""
    batch, length = int(config["batch"]), int(config["window_length"])
    return {"bytes": batch * (8 + 4 + 4 + 8) + batch * (length * 4 + 4),
            "flops": batch * length, "bound": "bytes"}
