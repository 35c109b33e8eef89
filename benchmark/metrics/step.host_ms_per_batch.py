"""Driver + step, host side: the driver's dispatch + fence + decode seconds
per batch (``driver.step_seconds / batches_stepped``), over the window."""


def read(run):
    batches = run.delta("driver.batches_stepped")
    return run.delta("driver.step_seconds") / batches * 1e3 if batches \
        else None
