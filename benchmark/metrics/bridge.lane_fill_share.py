"""Bridge: events stepped over the cells of the static lane grids that
carried them (``probe.events / (probe.steps * lanes * lane_batch)``), over
the window: what key skew costs, since the fullest lane seals the batch for
every lane. Nothing to read in a configuration without lanes."""


def read(run):
    steps = run.delta("probe.steps")
    cfg = run.cell.config
    if not steps or "lanes" not in cfg or "lane_batch" not in cfg:
        return None
    return run.delta("probe.events") \
        / (steps * int(cfg["lanes"]) * int(cfg["lane_batch"])) * 100.0
