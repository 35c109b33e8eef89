"""The arithmetic of the end-to-end metrics, kept apart so it can be tested
on hand-made samples."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(sample, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty sample."""
    xs = np.asarray(sample, dtype=np.float64)
    if xs.size == 0:
        raise ValueError("percentile of an empty sample")
    k = min(xs.size - 1, max(0, int(np.ceil(q / 100.0 * xs.size)) - 1))
    return float(np.partition(xs, k)[k])    # millions of rows: no full sort


def events_done(stamps, last_event, t: float) -> int:
    """Events whose results had reached the callback by time ``t``: one past
    the newest contributing event among the rows stamped at or before ``t``
    (0 where none had). ``last_event[r]`` is the index of the last event that
    contributed to row ``r``; ``stamps[r]`` is when the callback got it."""
    stamps = np.asarray(stamps)
    done = stamps <= t
    if not done.any():
        return 0
    return int(np.asarray(last_event)[done].max()) + 1


def throughput_eps(stamps, last_event, t_open: float, t_close: float) -> float:
    """All events whose results were delivered inside the window over the
    whole window's seconds: a stall lowers it, nothing is sliced away."""
    return (events_done(stamps, last_event, t_close)
            - events_done(stamps, last_event, t_open)) / (t_close - t_open)


def latencies_ms(stamps, due) -> np.ndarray:
    """Row latency: callback stamp minus the due time of the last
    contributing event, in ms."""
    return (np.asarray(stamps) - np.asarray(due)) * 1e3


def p50_drift_pct(lat_ms, due, t_open: float, t_close: float) -> float:
    """Median latency of the rows due in the window's second half over the
    first half's, minus one, in percent: above 0 means a backlog grows."""
    lat_ms, due = np.asarray(lat_ms), np.asarray(due)
    mid = (t_open + t_close) / 2.0
    first, second = lat_ms[due < mid], lat_ms[due >= mid]
    if first.size == 0 or second.size == 0:
        raise ValueError("a half of the window holds no row")
    return (percentile(second, 50) / percentile(first, 50) - 1.0) * 100.0


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles the driver uses."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
