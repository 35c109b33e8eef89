"""Bridge: the share of the window's flushes that sealed a batch short of
its capacity because the next event's lane was full (cause ``lane_full``
over every cause the probe counted). Nothing to read where no flush was
counted."""


def read(run):
    causes = run.delta_causes()
    total = sum(causes.values())
    if not total:
        return None
    return causes.get("lane_full", 0) / total * 100.0
