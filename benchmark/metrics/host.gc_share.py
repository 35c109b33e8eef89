"""Host runtime: seconds inside the collector (``gc.callbacks`` start to
stop, every generation) over the window. The collector stays on with its
default thresholds; the benchmark's own long-lived objects are frozen."""


def read(run):
    t_open, t_close = run.t_open, run.t_close
    pause = sum(d for _, t, d in run.gc_events if t_open <= t < t_close)
    return pause / (t_close - t_open) * 100.0 if pause else None
