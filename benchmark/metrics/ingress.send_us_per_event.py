"""Client / ingress: the benchmark's own span round each block of
``send``/``send_columns`` calls, summed over the window, per event."""


def read(run):
    inside, events = run.window_send_seconds_and_events()
    return inside / events * 1e6 if events else None
