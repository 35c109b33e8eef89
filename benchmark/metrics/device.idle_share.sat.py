"""Device: 1 - union of device operation intervals over the traced stretch
of a saturating cell. The configurations hold kilobytes of state where a
deployment holds many keys, so the host's share is larger here than there."""


def read(run):
    return run.idle_share_pct()
