"""Bridge: events stepped over the slots of the batches that carried them
(``probe.events / (probe.steps * capacity)``), over the window."""


def read(run):
    steps = run.delta("probe.steps")
    if not steps:
        return None
    return run.delta("probe.events") / (steps * run.batch_capacity) * 100.0
