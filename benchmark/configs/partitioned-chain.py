"""Plain reference of ``partitioned-chain``: the semantics written down, with
nothing of the program in it (its own copy: it imports no other reference).

``partition with (dev of S)`` round ``every e1=S[v > T] -> e2=S[v > e1.v]
-> ... -> e8=S[v > e7.v] within W``: every key matches its own chain, apart
from every other key's events. Within one key: every event over the
threshold opens a partial match; a partial waiting at state k takes the
key's first later event whose value exceeds its newest one (others are
skipped, `->` is followed-by); a partial whose first event is more than W
older than the current event is dropped. Timestamps are global and one
apart, so a match is the chain of "next greater" events of its key from its
first event, kept when the eighth is at most W events (of any key) after
the first.
"""

from __future__ import annotations

import numpy as np


def _served_from_the_chip_or_not_at_all() -> None:
    """This deployment exists only where a `partition with` block has a
    device branch. A program without one does not refuse the app text, not
    even under `strict='true'`: it deploys the block on the per-key
    interpreter (13,608 events/s, `setup_s` 172 s, nothing on the device,
    so a traced run ends in an exception; my chip run, PR 29, call 2). So
    the configuration refuses such a program itself, as the cell is loaded:
    another exit code than 0, within seconds. This is the one line of the
    file that looks at the program; `reference` and `least_work` do not."""
    try:
        from siddhi_tpu.core import device_bridge
    except ImportError:
        return      # no program beside the benchmark: run.py says so itself
    if not hasattr(device_bridge, "try_build_device_partition"):
        raise SystemExit(
            "benchmark: configuration 'partitioned-chain' is a `partition "
            "with` block served from the chip, and this program gives a "
            "Partition element no device branch (core/device_bridge.py has "
            "no try_build_device_partition). No result.")


_served_from_the_chip_or_not_at_all()


def reference(config: dict, cols: dict, n: int, dtype=np.float64) -> dict:
    """Rows for the stream's first ``n`` events. ``dtype`` is the precision
    values are held and compared in (the control passes a lower one)."""
    states = int(config["states"])
    v = np.asarray(cols["v"][:n], dtype=np.float64).astype(dtype)
    # every state's value exceeds the threshold, so only such events matter
    idx = np.flatnonzero(v > np.asarray(config["first_threshold"], dtype))
    _, key = np.unique(np.asarray(cols["dev"][:n])[idx].astype("U"),
                       return_inverse=True)
    # the events that matter, a key's together and in arrival order
    by_key = np.argsort(key, kind="stable")
    idx, key = idx[by_key], key[by_key]
    sub = v[idx].astype(np.float64).tolist()
    new_key = np.append(True, key[1:] != key[:-1]).tolist()
    nxt = np.full(len(sub) + 1, len(sub), dtype=np.int64)   # sentinel: none
    stack: list = []
    for j, x in enumerate(sub):
        if new_key[j]:
            stack.clear()       # a chain never crosses keys
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
    events in (value f32 + key code i32 + timestamp i64), every lane's
    tables read and written once (``lanes x slots`` slots per waiting state,
    state k holding k values f32, the key's code i32, its first timestamp
    i64 and a flag: 4 k + 13 bytes), each event compared with each slot of
    its own lane's waiting states. Rows out are data-dependent and left out
    (a lower bound)."""
    batch, slots, states, lanes = (int(config[k]) for k in
                                   ("batch", "slots", "states", "lanes"))
    state_bytes = sum(lanes * slots * (4 * k + 13) for k in range(1, states))
    return {"bytes": batch * (4 + 4 + 8) + 2 * state_bytes,
            "flops": batch * slots * (states - 1), "bound": "bytes"}
