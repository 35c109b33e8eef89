"""End to end, not judged: 99th percentile of the sample ``latency_p50_ms``
is taken from."""

from harness.stats import percentile


def read(run):
    lat = run.latency_sample()
    return percentile(lat[0], 99) if lat is not None else None
