"""What decides ``correct``: every number compared, each beside its limit.

Two parts. ``compare_rows`` holds the rows the timed path delivered against
the plain reference's rows for the same events (all of them, not a sample).
``served_numbers`` reads what a fallback would hide while the rows stay
right: the checks of ``chip_smoke.py``'s ``served_failures``, copied here as
numbers with the limit 0 each.
"""

from __future__ import annotations

import logging

import numpy as np

# overflow counters of the device kernels (core/device_bridge.py warns on
# them at drain points; here any non-zero value fails the run outright)
OVERFLOW_COUNTERS = ("window_drops", "group_collisions", "ts_regressions",
                     "drops", "join_drops", "ring_drops")


class WarningCounter(logging.Handler):
    """Counts WARNING-or-worse records of the ``siddhi_tpu`` loggers: that is
    where `device step failed`, `device dispatch failed` and the kernels'
    overflow warnings go."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: list = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")


def _as_table(columns: dict, names: list, n: int) -> np.ndarray:
    """(n, len(names)) float64 table; DOUBLE columns pass through float32,
    because the device computes DOUBLE in float32 and the configurations'
    values are chosen to be exact there."""
    out = np.empty((n, len(names)), dtype=np.float64)
    for j, name in enumerate(names):
        col = np.asarray(columns[name])[:n]
        if col.dtype.kind == "f" or str(col.dtype) == "bfloat16":
            col = col.astype(np.float32)
        out[:, j] = col
    return out


def compare_rows(ref: dict, got: dict, n_got: int) -> dict:
    """Rows delivered (``got``: columns, first ``n_got`` valid) against the
    reference's (``ref``: ``columns``, ``last_event``, ``ordered``).

    Returns ``rows_missing``, ``rows_extra``, ``rows_wrong`` and ``map``:
    for each delivered row the index of the reference row it is, or -1.
    Ordered output is compared place by place; unordered output (a pattern
    may emit the matches of one event in any order) as multisets of rows,
    where what is left over on both sides counts as rows wrong.
    """
    names = list(ref["columns"])
    n_ref = len(ref["last_event"])
    a = _as_table(ref["columns"], names, n_ref)
    b = _as_table(got, names, n_got)
    if ref["ordered"]:
        m = min(n_ref, n_got)
        same = np.all(a[:m] == b[:m], axis=1)
        mapping = np.full(n_got, -1, dtype=np.int64)
        mapping[:m] = np.where(same, np.arange(m), -1)
        return {"rows_missing": max(0, n_ref - n_got),
                "rows_extra": max(0, n_got - n_ref),
                "rows_wrong": int(m - same.sum()), "map": mapping}
    # unordered: sort both by content (ties keep their order: reference rows
    # by emission, delivered rows by arrival), then walk the two sorted lists
    oa = np.lexsort(a.T[::-1]) if n_ref else np.zeros(0, dtype=np.int64)
    ob = np.lexsort(b.T[::-1]) if n_got else np.zeros(0, dtype=np.int64)
    sa, sb = a[oa], b[ob]
    mapping = np.full(n_got, -1, dtype=np.int64)
    if n_ref == n_got and np.array_equal(sa, sb):
        mapping[ob] = oa
        return {"rows_missing": 0, "rows_extra": 0, "rows_wrong": 0,
                "map": mapping}
    i = j = missing = extra = 0
    while i < n_ref and j < n_got:
        ra, rb = tuple(sa[i]), tuple(sb[j])
        if ra == rb:
            mapping[ob[j]] = oa[i]
            i += 1
            j += 1
        elif ra < rb:
            missing += 1
            i += 1
        else:
            extra += 1
            j += 1
    # a reference row nothing equals and a delivered row that equals nothing
    # pair off as one row wrong (an answer altered is one answer, not two)
    missing, extra = missing + (n_ref - i), extra + (n_got - j)
    wrong = min(missing, extra)
    return {"rows_missing": missing - wrong, "rows_extra": extra - wrong,
            "rows_wrong": wrong, "map": mapping}


def served_numbers(rt, sent: int, platform: str) -> dict:
    """Numbers (limit 0 each) that are non-zero when the device path did not
    do the work: after the events went in and ``flush_device()`` returned."""
    import jax

    out = {"device_bridges_not_1": int(len(rt.device_bridges) != 1),
           "host_tiers_built": 0, "guard_missing": 0, "guard_failures": 0,
           "guard_fallback_events": 0, "guard_lost_events": 0,
           "guard_circuit_open": 0, "state_leaves_off_device": 0,
           "events_unaccounted": 0, "overflow_counters": 0}
    if len(rt.device_bridges) != 1:
        return out
    out["host_tiers_built"] = sum(
        1 for tier in (rt.host_bridges, rt.fleet_bridges, rt.query_runtimes,
                       rt.partition_runtimes) if tier)
    bridge = rt.device_bridges[0]
    if bridge.guard is None:
        out["guard_missing"] = 1
    else:
        rep = bridge.guard.report()
        out["guard_failures"] = int(rep["failures"])
        out["guard_fallback_events"] = int(rep["fallback_events"])
        out["guard_lost_events"] = int(rep["lost_events"])
        out["guard_circuit_open"] = int(rep["circuit"] != "closed")
    state = bridge.runtime.state
    for leaf in jax.tree_util.tree_leaves(state):
        where = {d.platform for d in leaf.devices()} \
            if isinstance(leaf, jax.Array) else {type(leaf).__name__}
        if where != {platform}:
            out["state_leaves_off_device"] += 1
    probe = bridge.probe
    if probe is None or probe.steps <= 0:
        out["events_unaccounted"] = sent
    else:
        out["events_unaccounted"] = abs(int(probe.events) - sent)
    out["overflow_counters"] = overflow_count(state)
    return out


def overflow_count(state) -> int:
    """Sum of the kernels' overflow counters in a runtime's state, whatever
    their shape: a scalar in a single query's state, one count per key lane
    (``[P]``) in lane-stacked state."""
    import jax

    return sum(int(np.sum(jax.device_get(state[key])))
               for key in OVERFLOW_COUNTERS if key in state)
