"""Jitted step, device side: device busy time in the traced stretch over the
steps that ran in it (runs of the module that took most device time)."""


def read(run):
    s = run.device_seconds_per_batch()
    return s * 1e3 if s else None
