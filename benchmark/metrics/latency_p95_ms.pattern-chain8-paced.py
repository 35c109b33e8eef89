"""End to end, not judged in this cell: 95th percentile of the sample
``latency_p50_ms`` is taken from. The machines freeze for about 110 ms
nought to six times in a window, which puts nought to six percent of this
cell's rows into the tail: right round the 95th percentile, so it reads 46
or 110 ms by the machine's doing (PERF.md, PR 24)."""

from harness.stats import percentile


def read(run):
    lat = run.latency_sample()
    return percentile(lat[0], 95) if lat is not None else None
