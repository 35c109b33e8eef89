"""Driver + step, host side: dispatch + fence + decode, what a sealed batch
takes from the start of its dispatch to its rows, the waits between them
left out (the program's ``device_step``, ``egress_fence`` and
``egress_decode`` phase trackers). Event-weighted mean over the window's
batches."""

PHASES = ("device_step", "egress_fence", "egress_decode")


def read(run):
    n = run.delta("phase.device_step.count")
    sums = [run.delta(f"phase.{p}.sum") for p in PHASES]
    if not n or None in sums:
        return None
    return sum(sums) / n * 1e3
