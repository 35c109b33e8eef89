"""From a profiler trace to numbers: device busy time, the operations that
took most of it, and the idle gaps by what the host was doing in them.

``extract`` turns an ``.xplane.pb`` into plain lists (planes, their lines,
events as ``[name, start_ns, duration_ns]``); ``reduce`` works on that form
alone, so it is tested on a small recording kept as JSON
(``tests/data/trace_small.json``).
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host activity, most telling first: a gap is charged to the first of these
# that was going on in it. While the driver thread is inside the program
# (dispatching, fencing and decoding, or publishing rows) the device waits
# for that; otherwise it waits for the client to fill a batch.
ACTIVITY_PREFIXES = ("siddhi:", "bench:")
MIN_GAP_NS = 2_000


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def extract(xplane_path: str) -> dict:
    """Device planes whole; host planes cut down to the benchmark's own
    annotations (the host tracer also records every TraceMe of the runtime)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith(DEVICE_PREFIXES)
        lines = []
        for line in plane.lines:
            # a device operation is named by its whole HLO line
            # (`%fusion.61 = (u32[1024]{...}) fusion(...)`): keep the name
            events = [[ev.name.split(" = ", 1)[0].lstrip("%"),
                       int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith(ACTIVITY_PREFIXES)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _charge_gaps(gaps: list, activity: dict) -> dict:
    """Seconds of ``gaps`` by host activity: each instant of a gap goes to
    the first name of ``activity`` (already in order of precedence) whose
    intervals cover it, the rest to ``unattributed``."""
    names = list(activity)
    points = []     # (time, kind, delta): kind -1 = gap, else index of name
    for s, e in gaps:
        points += [(s, -1, 1), (e, -1, -1)]
    for k, name in enumerate(names):
        for s, e in activity[name]:
            points += [(s, k, 1), (e, k, -1)]
    points.sort()
    depth = [0] * len(names)
    in_gap = 0
    charged = {name: 0 for name in names}
    charged["unattributed"] = 0
    last = None
    for t, kind, delta in points:
        if in_gap and last is not None and t > last:
            owner = next((names[k] for k in range(len(names)) if depth[k]),
                         "unattributed")
            charged[owner] += t - last
        if kind < 0:
            in_gap += delta
        else:
            depth[kind] += delta
        last = t
    return {k: v / 1e9 for k, v in charged.items() if v}


def reduce(trace: dict, top: int = 10) -> dict:
    """``busy_s`` (mean over device planes of the union of their operations'
    intervals), ``window_s`` (first to last thing traced), ``steps`` (runs of
    the module that took most device time), ``device_ops`` and ``idle_gaps``
    (``[[name, seconds], ...]``, at most ``top`` each). Raises where no
    operation ran on a device: such a trace measures nothing."""
    dev_planes = [p for p in trace["planes"]
                  if p["name"].startswith(DEVICE_PREFIXES)]
    host_planes = [p for p in trace["planes"] if p not in dev_planes]
    activity_raw: dict = {}
    for plane in host_planes:
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name.startswith(ACTIVITY_PREFIXES):
                    activity_raw.setdefault(name, []).append([s, s + d])
    order = sorted(activity_raw, key=lambda n: (
        next(i for i, p in enumerate(ACTIVITY_PREFIXES) if n.startswith(p)),
        n))
    activity = {n: _union(activity_raw[n]) for n in order}

    per_plane = []
    op_seconds: dict = {}
    module_seconds: dict = {}
    module_runs: dict = {}
    for plane in dev_planes:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE)
        if ops is None:     # a backend that names its lines otherwise
            ops = [ev for name, evs in lines.items()
                   if name != MODULES_LINE for ev in evs]
        if not ops:
            continue
        per_plane.append(_union([[s, s + d] for _, s, d in ops]))
        for name, _, d in ops:
            op_seconds[name] = op_seconds.get(name, 0) + d
        for name, _, d in lines.get(MODULES_LINE, []):
            module_seconds[name] = module_seconds.get(name, 0) + d
            module_runs[name] = module_runs.get(name, 0) + 1
    if not per_plane:
        raise ValueError("the trace holds no device operation")

    starts = [iv[0][0] for iv in per_plane] + \
        [iv[0][0] for iv in activity.values() if iv]
    ends = [iv[-1][1] for iv in per_plane] + \
        [iv[-1][1] for iv in activity.values() if iv]
    w0, w1 = min(starts), max(ends)
    busy_ns = sum(sum(e - s for s, e in iv) for iv in per_plane) \
        / len(per_plane)

    # gaps of the first device plane (one chip today; with more, the gaps of
    # each would want a table of their own)
    busy = per_plane[0]
    gaps = []
    edge = w0
    for s, e in busy:
        if s - edge >= MIN_GAP_NS:
            gaps.append([edge, s])
        edge = max(edge, e)
    if w1 - edge >= MIN_GAP_NS:
        gaps.append([edge, w1])
    charged = _charge_gaps(gaps, activity)
    idle = sorted(charged.items(), key=lambda kv: -kv[1])[:top - 1]
    idle.append(("longest_gap",
                 max((e - s for s, e in gaps), default=0) / 1e9))

    steps = 0
    if module_seconds:
        steps = module_runs[max(module_seconds, key=module_seconds.get)]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "steps": steps,
        "device_ops": [[n, s / 1e9] for n, s in sorted(
            op_seconds.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for n, s in idle],
    }
