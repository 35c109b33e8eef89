"""Plain reference of ``pattern-chain8``: the semantics written down, with
nothing of the program in it.

``every e1=S[v > T] -> e2=S[v > e1.v] -> ... -> e8=S[v > e7.v] within W``:
every event over the threshold opens a partial match; a partial waiting at
state k takes the first later event whose value exceeds its newest one
(others are skipped, `->` is followed-by); a partial whose first event is
more than W older than the current event is dropped. With timestamps one
apart, a match is the chain of "next greater" events from its first event,
kept when the eighth is at most W events after the first.
"""

from __future__ import annotations

import numpy as np


def reference(config: dict, cols: dict, n: int, dtype=np.float64) -> dict:
    """Rows for the stream's first ``n`` events. ``dtype`` is the precision
    values are held and compared in (the control passes a lower one)."""
    states = int(config["states"])
    v = np.asarray(cols["v"][:n], dtype=np.float64).astype(dtype)
    # every state's value exceeds the threshold, so only such events matter
    idx = np.flatnonzero(v > np.asarray(config["first_threshold"], dtype))
    sub = v[idx].astype(np.float64).tolist()
    nxt = np.full(len(sub) + 1, len(sub), dtype=np.int64)   # sentinel: none
    stack: list = []
    for j, x in enumerate(sub):
        while stack and sub[stack[-1]] < x:
            nxt[stack.pop()] = j
        stack.append(j)
    hops = [np.arange(len(sub), dtype=np.int64)]
    for _ in range(states - 1):
        hops.append(nxt[hops[-1]])
    ok = hops[-1] < len(sub)
    idx_pad = np.append(idx, np.iinfo(np.int64).max // 2)
    ok &= idx_pad[hops[-1]] - idx <= int(config["within_ms"])
    last = idx_pad[hops[-1]][ok]
    order = np.argsort(last, kind="stable")     # emission: by closing event
    vals = v.astype(np.float64)
    columns = {f"v{k + 1}": vals[idx_pad[hops[k]][ok]][order]
               for k in range(states)}
    return {"columns": columns, "last_event": last[order], "ordered": False}


def least_work(config: dict) -> dict:
    """Least bytes and operations one batch needs by the query's semantics:
    events in (value f32 + timestamp i64), every live partial read and
    written once (``slots`` per waiting state, state k holding k values f32
    and its first timestamp i64), each event compared with each slot of each
    waiting state. Rows out are data-dependent and left out (a lower bound)."""
    batch, slots, states = (int(config[k]) for k in
                            ("batch", "slots", "states"))
    state_bytes = sum(slots * (4 * k + 8) for k in range(1, states))
    return {"bytes": batch * (4 + 8) + 2 * state_bytes,
            "flops": batch * slots * (states - 1), "bound": "bytes"}
