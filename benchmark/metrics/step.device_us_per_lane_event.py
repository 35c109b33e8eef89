"""Kernel: what one event's turn of the per-event scan costs across all
lanes. The device time of one batch in the trace
(``run.device_seconds_per_batch()``) over the configuration's ``lane_batch``,
the scan's sequential depth: a lane's batch is walked one event at a time,
every lane at once, so a batch costs ``lane_batch`` turns whatever it
holds. The number a change to the scan body moves and a change of the
depth does not. Nothing to read without a trace or in a configuration
without lanes."""


def read(run):
    s = run.device_seconds_per_batch()
    depth = run.cell.config.get("lane_batch")
    return s / int(depth) * 1e6 if s and depth else None
