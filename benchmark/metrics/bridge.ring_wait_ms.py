"""Bridge: the client's wait in ``AsyncDeviceDriver.submit`` on a full ring
(the program's ``ring_wait`` phase tracker, span
``siddhi:submit.ring_wait``), spread over every event stepped in the window:
0.0 where no batch waited."""


def read(run):
    waited, events = run.delta("phase.ring_wait.sum"), run.delta("probe.events")
    return waited / events * 1e3 if waited is not None and events else None
