"""Kernel: the least time the chip could take for one batch (the
configuration's ``least_work`` over the published peaks of this
``device_kind``) over the device time one batch took in the trace."""

from harness.peaks import roofline_share_pct


def read(run):
    s = run.device_seconds_per_batch()
    if not s:
        return None
    return roofline_share_pct(run.cell.reference.least_work(run.cell.config),
                              run.device_kind, s)
