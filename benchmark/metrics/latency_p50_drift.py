"""End to end, not judged: median latency of the rows due in the window's
second half over the first half's, minus one. Flat (about 0) below the knee;
it climbs where a backlog grows through the run."""

from harness.stats import p50_drift_pct


def read(run):
    lat = run.latency_sample()
    if lat is None:
        return None
    return p50_drift_pct(lat[0], lat[1], run.t_open, run.t_close)
