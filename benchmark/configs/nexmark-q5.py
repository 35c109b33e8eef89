"""Plain reference of ``nexmark-q5``: the semantics written down, with
nothing of the program in it (its own copy: it imports no other reference).

NEXmark Query 5, Hot Items: ``from Bid#window.hopping(D, H) select auction,
count() as num group by auction order by num desc limit 1``. Event ``i``
carries the timestamp ``base + i``, so times are event indices here. The
first event arms the first boundary at its timestamp + H, and boundaries
follow every H. A boundary ``t`` fires when an event with a timestamp >= t
arrives, before that event joins the window; its window is the events with
``t - D < ts < t``; its row is the auction with the most bids there and
that count, a tie going to the auction whose FIRST bid in the window is the
earliest; a boundary with an empty window emits nothing.

Written as the semantics read (``_boundary_walk``: one ``np.unique`` over the
window a boundary, kept as the second witness of a test), then made fast:
bids are counted once, by panes of ``gcd(D, H)`` events, and a boundary
merges the ``D / gcd`` panes its window spans.
"""

from __future__ import annotations

import math

import numpy as np


def _served_from_the_chip_or_not_at_all() -> None:
    """This deployment exists only where the device serves a grouped hopping
    flush with the selector's tail. A program that does not refuses the app
    text at deployment (``strict='true'``: DeviceCompileError), after the
    pool has been drawn; the configuration refuses such a program itself, as
    the cell is loaded: another exit code than 0, within seconds. This is
    the one function of the file that looks at the program; ``reference``
    and ``least_work`` do not."""
    try:
        from siddhi_tpu.tpu import query_compile
    except ImportError:
        return      # no program beside the benchmark: run.py says so itself
    if not hasattr(query_compile, "selector_tail_refusal"):
        raise SystemExit(
            "benchmark: configuration 'nexmark-q5' is a grouped hopping "
            "window with `order by` / `limit` served from the chip, and "
            "this program keeps both on the host path (tpu/query_compile.py "
            "has no selector_tail_refusal). No result.")


_served_from_the_chip_or_not_at_all()


def _boundaries(n: int, hop: int) -> np.ndarray:
    """The boundaries that fire among ``n`` events a tick apart: H, 2H, ..
    up to the last event's index (the event AT a boundary fires it)."""
    return np.arange(hop, n, hop, dtype=np.int64)


def _top(keys, first, counts, dtype):
    """The row of one window: ``counts`` held in ``dtype``; the highest,
    ties to the key first seen."""
    held = counts.astype(dtype).astype(np.float64)
    tied = np.flatnonzero(held == held.max())
    j = tied[np.argmin(first[tied])]
    return keys[j], int(held[j])


def _boundary_walk(auction: np.ndarray, duration: int, hop: int, n: int,
                   dtype=np.float64) -> list:
    """``(boundary, auction, num)`` of every boundary, the slow way: each
    boundary looks at its whole window."""
    rows = []
    for t in _boundaries(n, hop).tolist():
        lo = max(0, t - duration + 1)
        if t - 1 < lo:
            continue
        keys, first, counts = np.unique(auction[lo:t], return_index=True,
                                        return_counts=True)
        rows.append((t, *_top(keys, first, counts, dtype)))
    return rows


def reference(config: dict, cols: dict, n: int, dtype=np.float64) -> dict:
    """Rows for the stream's first ``n`` events. ``dtype`` is the precision
    counts are HELD in (the control passes a lower one: bfloat16 rounds a
    count of 800). ``last_event`` of a row is the event whose arrival fires
    its boundary: the row cannot exist before that event is in."""
    duration, hop = int(config["window_ms"]), int(config["hop_ms"])
    auction = np.asarray(cols["auction"][:n]).astype(np.int64)
    pane = math.gcd(duration, hop)
    # pane 0 is event 0 alone, pane p >= 1 the events ((p - 1) * pane,
    # p * pane]: a window (t - D, t) is then whole panes but for the event
    # AT t, the last of its last pane, which fires the boundary and is not
    # in it
    edges = np.append(0, np.arange(1, n + pane, pane)).clip(max=n)
    panes = []
    for a, b in zip(edges[:-1].tolist(), edges[1:].tolist()):
        keys, first, counts = np.unique(auction[a:b], return_index=True,
                                        return_counts=True)
        panes.append((keys, first + a, counts))
    out_t, out_key, out_num = [], [], []
    for t in _boundaries(n, hop).tolist():
        q = t // pane
        span = panes[max(0, q - duration // pane + 1):q + 1]
        keys = np.concatenate([p[0] for p in span])
        first = np.concatenate([p[1] for p in span])
        counts = np.concatenate([p[2] for p in span])
        keys, inverse = np.unique(keys, return_inverse=True)
        merged = np.bincount(inverse, weights=counts,
                             minlength=len(keys)).astype(np.int64)
        seen = np.full(len(keys), n, dtype=np.int64)
        np.minimum.at(seen, inverse, first)
        merged[np.searchsorted(keys, auction[t])] -= 1     # the event at t
        live = merged > 0
        if not live.any():
            continue
        key, num = _top(keys[live], seen[live], merged[live], dtype)
        out_t.append(t)
        out_key.append(key)
        out_num.append(num)
    return {"columns": {"auction": np.array(out_key, dtype=np.int64),
                        "num": np.array(out_num, dtype=np.int64)},
            "last_event": np.array(out_t, dtype=np.int64), "ordered": True}


def least_work(config: dict) -> dict:
    """Least bytes and operations one batch needs by the query's semantics:
    the events in (auction i64 + timestamp i64) and the same written once
    into the window; and the ``batch / hop`` of a boundary that a batch
    carries: the window's keys read once (8 B an event), one count read and
    written per live key (8 B each way), ``limit`` rows out (auction i64 +
    num i64). Per event a compare and an add; per live key a compare for the
    maximum."""
    batch, duration, hop, keys = (int(config[k]) for k in (
        "batch", "window_ms", "hop_ms", "live_keys"))
    share = batch / hop
    return {"bytes": int(batch * 16 * 2
                         + share * (8 * duration + 16 * keys + 16)),
            "flops": int(batch * 2 + share * keys), "bound": "bytes"}
