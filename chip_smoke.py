#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that siddhi_tpu still starts on the chip.

One process drives the device path once, through the entry points a user
calls, at the sizes the repo's flagship deployments use, and compares every
row that comes out with the scalar interpreter (the same app text without
``@device``, the same events):

- S1  served, single stream: BASELINE.json config #1's shape (filter +
      ``window.length(1000)`` + group-by aggregate), 1,000,000 events through
      ``InputHandler.send_columns``;
- S2  served, pattern: the north star's 8-state rising chain without the
      partition wrapper, 200,000 events through per-event ``InputHandler.send`` (the
      bridge's only ingress for pattern/join queries today), blocked kernel;
- S3  the flagship kernel at the north star's shape: ``PartitionedNFARuntime``,
      64 lanes x 2048 x 8 states, 1,000,000 events over 1,024 keys through the
      C++ ingress built in this run;
- S3b served, partition: ``S3_APP`` with an ``@device(...)`` line deployed
      through ``SiddhiManager`` (the device branch of a ``partition with``
      block: one bridge, driver, probe and guard over lane-stacked tables),
      S3's oracle prefix through ``send_columns``, rows held to the
      interpreter's; then the same stream through the Kleene-closure block
      of ``benchmark/configs/partitioned-kleene.siddhi`` (a count state: the
      lanes step the per-event scan kernel), held to the interpreter too;
- S4  S3 again with its lanes sharded over four chips (skipped on one);
- S5  a compile sweep over every kind the device compilers accept;
- S6  served, grouped hopping window with the selector's tail: NEXmark
      Query 5 (``benchmark/configs/nexmark-q5.small.siddhi``'s query),
      100,000 bids of 4,096 auctions (ids above 2^33) through
      ``send_columns``: one row a boundary, the top auction by count;
- S7  served, keyed sliding window: the Siddhi guide's value partition
      (``partition with (deviceID of TempStream)`` round
      ``window.length(10)`` + ``max(temp)``, the benchmark's ``having``),
      100,000 readings of 4,096 devices (ids above 2^40) through
      ``send_columns``; the table's gauges ``keyed_live_keys`` and
      ``key_table_fill_share`` are printed.

The served stages also read what a fallback would hide: the DeviceGuard's
counters, where the state lives, the probe's step and event counts, the
kernels' overflow counters, and every WARNING on the ``siddhi_tpu`` loggers.
Any of them off fails the stage even when the rows are right.

    python3 chip_smoke.py [--seed N]            the check, full size, needs a TPU
    python3 chip_smoke.py --rehearsal [...]     reduced size on whatever JAX
                                                finds; every line says so and
                                                no result line is printed
    python3 chip_smoke.py --only S3,S5          a partial run is never a pass

It states counts and each stage's first step in seconds (`first_step_s`: the
trace and compile, or the program's load from the compile cache), never a
rate or a latency.
Exit status: 0 only when every stage passed on a TPU (or, with --rehearsal,
on the platform found). The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import logging
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "tests"))     # util_parity's tolerance

FULL = {
    "s1_events": 1_000_000, "s1_groups": 500, "s1_batch": 8192,
    "s2_events": 200_000, "s2_batch": 2048, "s2_slots": 1024,
    "s2_min_rows": 1000,
    "s3_events": 1_000_000, "s3_keys": 1024, "s3_lanes": 64,
    "s3_lane_batch": 2048, "s3_slots": 512, "s3_oracle": 200_000,
    "s3_min_rows": 1000,
    "s3b_batch": 32768, "s3b_kleene_events": 100_000,
    "s6_events": 100_000, "s7_events": 100_000,
}
# --rehearsal: the same stages and shapes with the stream cut short and the
# flagship's lane grid shrunk, so a CPU gets through in half a minute.
REHEARSAL = {
    **FULL,
    "s1_events": 30_000,
    "s2_events": 8_000, "s2_min_rows": 1,
    "s3_events": 40_000, "s3_keys": 128, "s3_lanes": 8,
    "s3_lane_batch": 256, "s3_oracle": 12_000, "s3_min_rows": 1,
    "s3b_batch": 1024, "s3b_kleene_events": 6_000,
    "s6_events": 30_000, "s7_events": 30_000,
}
N_STATES = 8
# overflow counters of the device kernels (core/device_bridge.py warns on
# them at drain points; here any non-zero value fails the stage outright)
OVERFLOW_COUNTERS = ("window_drops", "group_collisions", "ts_regressions",
                     "drops", "join_drops", "ring_drops")

_prefix = ""


def say(msg: str) -> None:
    print(_prefix + msg, flush=True)


class _Warnings(logging.Handler):
    """Collects every WARNING-or-worse record of the ``siddhi_tpu`` loggers:
    that is where `device step failed`, `device dispatch failed` and the
    kernels' overflow warnings go."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: list = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")

    def drain(self) -> list:
        out, self.records = self.records, []
        return out


# ---------------------------------------------------------------------------
# checks (importable: tests/test_chip_smoke.py pins the hidden-fallback case)
# ---------------------------------------------------------------------------

def off_platform(state, platform: str) -> list:
    """Leaves of a device state pytree that are not jax Arrays on
    ``platform``."""
    import jax

    bad = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        where = {d.platform for d in leaf.devices()} \
            if isinstance(leaf, jax.Array) else {type(leaf).__name__}
        if where != {platform}:
            bad.append(f"state{jax.tree_util.keystr(path)} lives on "
                       f"{sorted(where)}, not on {platform}")
    return bad


def served_failures(rt, sent: int, platform: str) -> list:
    """What is wrong with a served ``@device`` app after its events went in
    and ``flush_device()`` returned — everything a fallback would hide while
    the rows stay right. ``sent`` is the number of events fed to the (single)
    device query; ``platform`` is where its state has to live."""
    bad = []
    if len(rt.device_bridges) != 1:
        return [f"{len(rt.device_bridges)} device bridges (want 1)"]
    others = {"host_bridges": rt.host_bridges,
              "fleet_bridges": rt.fleet_bridges,
              "query_runtimes": rt.query_runtimes,
              "partition_runtimes": rt.partition_runtimes}
    for name, tier in others.items():
        if tier:
            bad.append(f"{name} not empty: the query also built on a host "
                       f"tier")
    bridge = rt.device_bridges[0]
    if bridge.guard is None:
        bad.append("no DeviceGuard installed (not the production path)")
    else:
        rep = bridge.guard.report()
        for key in ("failures", "fallback_events", "lost_events"):
            if rep[key] != 0:
                bad.append(f"guard.{key} == {rep[key]} (a device step failed "
                           f"and was replayed on the host)")
        if rep["circuit"] != "closed":
            bad.append(f"guard circuit is {rep['circuit']}")
    state = bridge.runtime.state
    bad += off_platform(state, platform)
    probe = bridge.probe
    if probe is None or probe.steps <= 0:
        bad.append("probe saw no device step")
    elif probe.events != sent:
        bad.append(f"probe.events == {probe.events}, sent {sent}")
    import numpy as np
    for key in OVERFLOW_COUNTERS:       # a scalar, or one count a key lane
        if key in state and int(np.sum(state[key])) != 0:
            bad.append(f"{key} == {int(np.sum(state[key]))}")
    return bad


def reference_failures(rt) -> list:
    """The reference run has to be the scalar interpreter and nothing else."""
    tiers = (rt.device_bridges, rt.host_bridges, rt.fleet_bridges)
    if any(tiers) or not (rt.query_runtimes or rt.partition_runtimes):
        return ["the reference did not build on the scalar interpreter"]
    return []


def rows_failures(expected: list, actual: list, ordered: bool) -> list:
    """Rows against the interpreter's, with the f32 tolerance
    tests/util_parity.py uses (the device computes DOUBLE in float32)."""
    from util_parity import assert_rows_match, rows_equal

    if len(expected) != len(actual):
        return [f"{len(actual)} rows, the interpreter emitted "
                f"{len(expected)}"]
    if ordered:
        for i, (e, a) in enumerate(zip(expected, actual)):
            if not rows_equal(e, a):
                return [f"row {i}: device {a} != interpreter {e}"]
        return []
    try:
        assert_rows_match(expected, actual)
    except AssertionError as e:
        return [str(e)[:500]]
    return []


# ---------------------------------------------------------------------------
# running an app through the normal entry points
# ---------------------------------------------------------------------------

def run_app(app: str, out_stream: str, feed, check=None):
    """Deploy ``app`` through SiddhiManager, ``feed(rt)`` it, drain, and
    return (rows, check(rt) or []). Rows and ``check`` are taken after
    ``flush_device()`` and before shutdown: what a stream that stopped
    sending still owes (an open timeBatch bucket) the device path emits at
    shutdown and the interpreter on a timer that playback never fires."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app, playback=True)
        rows: list = []
        rt.add_callback(out_stream, StreamCallback(
            lambda evs: rows.extend(e.data for e in evs)))
        rt.start()
        feed(rt)
        rt.flush_device()
        drained = list(rows)
        found = check(rt) if check is not None else []
    finally:
        m.shutdown()
    return drained, found


def run_pair(app: str, device_ann: str, out_stream: str, feed, sent: dict,
             platform: str, ordered: bool, warnings: _Warnings,
             also=None):
    """The same app text with and without ``device_ann``, the same feed
    (``sent``: events per stream). ``also(rt)`` adds a stage's own checks on
    the device runtime. Returns (failures, facts)."""
    facts = {}

    def check(rt):
        facts["events"] = sum(sent[s] for b in rt.device_bridges
                              for s in b.stream_ids)
        bad = served_failures(rt, facts["events"], platform)
        if rt.device_bridges and rt.device_bridges[0].probe is not None:
            probe = rt.device_bridges[0].probe
            facts["steps"] = probe.steps
            facts["first_step_s"] = round(probe.first_step_seconds, 2)
        return bad + (also(rt) if also is not None else [])

    warnings.drain()
    dev_rows, bad = run_app(app.format(device=device_ann), out_stream, feed,
                            check)
    bad += [f"logged: {w}" for w in warnings.drain()]
    ref_rows, ref_bad = run_app(app.format(device=""), out_stream, feed,
                                reference_failures)
    differ = rows_failures(ref_rows, dev_rows, ordered)
    facts.update(rows=len(dev_rows), rows_equal=not differ)
    return bad + ref_bad + differ, facts


# ---------------------------------------------------------------------------
# S1 — served, single stream
# ---------------------------------------------------------------------------

S1_APP = """
define stream Bids (auction int, bidder int, price double);
{device}
from Bids[price > 10.0]#window.length(1000)
select auction, sum(price) as total, count() as n
group by auction
insert into Stats;
"""


def stage_s1(cfg, seed, platform, warnings, keep):
    import numpy as np

    n, chunk = cfg["s1_events"], 8192
    rng = np.random.default_rng(seed)
    cols = {
        "auction": rng.integers(0, cfg["s1_groups"], n).astype(np.int32),
        "bidder": rng.integers(0, 100_000, n).astype(np.int32),
        # prices in quarters: every float32 partial sum of a window is exact,
        # so the sums must agree with the interpreter's doubles to the last
        # digit, on any backend and in any summation order
        "price": rng.integers(0, 401, n) / 4.0,
    }
    ts = 1_000_000 + np.arange(n, dtype=np.int64)

    def feed(rt):
        ih = rt.input_handler("Bids")
        for s in range(0, n, chunk):
            ih.send_columns({k: v[s:s + chunk] for k, v in cols.items()},
                            ts[s:s + chunk])

    def also(rt):
        # the filter rejects a tenth of every batch: each step's compaction
        # has rows to move, and says so
        bridge = rt.device_bridges[0]
        moves = bridge.runtime.step_gauges["compact_moves"]
        return [] if moves == bridge.probe.steps else [
            f"compact_moves reads {moves} of {bridge.probe.steps} steps"]

    ann = f"@device(strict='true', batch='{cfg['s1_batch']}')"
    return run_pair(S1_APP, ann, "Stats", feed, {"Bids": n}, platform, True,
                    warnings, also=also)


# ---------------------------------------------------------------------------
# S2 — served, pattern (blocked kernel)
# ---------------------------------------------------------------------------

def rising_chain(first: str, within: int, select_key: bool) -> str:
    """The N-state rising chain: e1 over a threshold, every later
    state above the one before it."""
    states = " -> ".join(
        f"e{i}=S[v > e{i - 1}.v]" if i > 1 else f"e1=S[{first}]"
        for i in range(1, N_STATES + 1))
    sel = ", ".join(f"e{i}.v as v{i}" for i in range(1, N_STATES + 1))
    if select_key:
        sel = "e1.dev as dev, " + sel
    return (f"from every {states} within {within}\n"
            f"select {sel} insert into Alerts;")


S2_APP = ("define stream S (dev string, v double);\n{device}\n"
          + rising_chain("v > 90.0", 4000, False))


def stage_s2(cfg, seed, platform, warnings, keep):
    import numpy as np

    n = cfg["s2_events"]
    rng = np.random.default_rng(seed + 1)
    # three decimals: distinct values stay distinct and ordered in float32
    vs = np.round(rng.uniform(0.0, 100.0, n), 3).tolist()
    devs = [f"dev{d}" for d in rng.integers(0, 256, n).tolist()]

    def feed(rt):
        send = rt.input_handler("S").send
        for i in range(n):
            send([devs[i], vs[i]], timestamp=1_000_000 + i)

    ann = (f"@device(strict='true', batch='{cfg['s2_batch']}', "
           f"slots='{cfg['s2_slots']}')")

    def blocked_kernel(rt):
        if all(b.runtime.compiler.blocked for b in rt.device_bridges):
            return []
        return ["the pattern did not take the blocked kernel"]

    bad, facts = run_pair(S2_APP, ann, "Alerts", feed, {"S": n}, platform,
                          False, warnings, also=blocked_kernel)
    if facts["rows"] < cfg["s2_min_rows"]:
        bad.append(f"{facts['rows']} rows < {cfg['s2_min_rows']}: the "
                   f"comparison would prove little")
    return bad, facts


# ---------------------------------------------------------------------------
# S3 / S4 — the flagship kernel at the north star's shape
# ---------------------------------------------------------------------------

S3_APP = ("define stream S (dev string, v double);\n"
          "partition with (dev of S)\nbegin\n"
          + rising_chain("v > 50.0", 60000, True) + "\nend;\n")


def flagship_events(cfg, seed):
    import numpy as np

    n, keys = cfg["s3_events"], cfg["s3_keys"]
    rng = np.random.default_rng(seed + 2)
    devs = rng.integers(0, keys, n).tolist()
    vs = np.round(rng.uniform(0.0, 100.0, n), 3).tolist()
    # 1 ms apart at 1,024 keys: each key sees about 58 events per `within`
    # window whatever the key count of the run
    step = max(1, 1024 // keys)
    return [(f"dev{d}", v, 1_000_000 + i * step)
            for i, (d, v) in enumerate(zip(devs, vs))]


def run_flagship(cfg, events, mesh=None):
    """Events through the C++ ingress into PartitionedNFARuntime. Returns
    (rows of the oracle prefix, all rows, runtime, facts)."""
    import jax
    from siddhi_tpu.tpu.partition import PartitionedNFARuntime

    rt = PartitionedNFARuntime(
        S3_APP, num_partitions=cfg["s3_lanes"], key_attr="dev",
        slot_capacity=cfg["s3_slots"], lane_batch=cfg["s3_lane_batch"],
        mesh=mesh)
    rt.enable_native_ingress()

    stepped = {"n": 0, "first_s": None}
    inner = rt._vstep

    def counted(*args):
        t0 = time.perf_counter()
        out = inner(*args)
        if stepped["n"] == 0:       # trace + compile + the first step
            jax.block_until_ready(out)
            stepped["first_s"] = round(time.perf_counter() - t0, 2)
        stepped["n"] += 1
        return out

    rt._vstep = counted

    def csv(evs):
        return "".join(f"{d},{v},{t}\n" for d, v, t in evs).encode()

    cut = cfg["s3_oracle"]
    head = rt.ingest_csv(csv(events[:cut]), ts_last=True, decode=True)
    head += rt.flush_native(decode=True) or []
    tail = rt.ingest_csv(csv(events[cut:]), ts_last=True, decode=True)
    tail += rt.flush_native(decode=True) or []
    facts = {"events": len(events), "rows": len(head) + len(tail),
             "steps": stepped["n"], "first_step_s": stepped["first_s"],
             "ingress": "native", "drops": rt.drop_count,
             "parse_errors": rt._ning.parse_errors,
             "state_bytes": sum(x.nbytes for x in
                                jax.tree_util.tree_leaves(rt.state))}
    return head, head + tail, rt, facts


def stage_s3(cfg, seed, platform, warnings, keep):
    import jax
    from siddhi_tpu import native

    so = native.so_path()
    built_here = not os.path.exists(so)
    if not native.native_available():
        # fail rather than pack in Python: the ingress is part of the path
        return [f"C++ ingress did not build: "
                f"{native.native_unavailable_reason()}"], {}
    events = flagship_events(cfg, seed)
    warnings.drain()
    head, rows, rt, facts = run_flagship(cfg, events)
    facts["so"] = os.path.relpath(so, REPO)
    facts["so_built_in_this_run"] = built_here
    bad = [f"logged: {w}" for w in warnings.drain()]
    bad += off_platform(rt.state, platform)
    if facts["drops"] or facts["parse_errors"]:
        bad.append(f"drops == {facts['drops']}, parse_errors == "
                   f"{facts['parse_errors']}")
    if rt.match_count != facts["rows"]:
        bad.append(f"match_count {rt.match_count} != {facts['rows']} rows "
                   f"decoded")
    if facts["rows"] < cfg["s3_min_rows"]:
        bad.append(f"{facts['rows']} rows < {cfg['s3_min_rows']}")

    cut = cfg["s3_oracle"]

    def feed(hrt):
        send = hrt.input_handler("S").send
        for d, v, t in events[:cut]:
            send([d, v], timestamp=t)

    ref, ref_bad = run_app(S3_APP, "Alerts", feed, reference_failures)
    differ = rows_failures(ref, head, ordered=False)
    bad += ref_bad + differ
    facts.update(oracle_events=cut, oracle_rows=len(ref),
                 rows_equal=not differ)
    stats = jax.devices()[0].memory_stats()
    if stats:
        facts["memory_stats"] = {k: stats[k] for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}
    keep["events"], keep["rows"] = events, rows
    keep["oracle_rows"] = ref
    return bad, facts


# the Kleene-closure block of benchmark/configs/partitioned-kleene.siddhi on
# S3_APP's stream and partition, at this stage's `within`
S3B_KLEENE_APP = (
    "define stream S (dev string, v double);\n"
    "partition with (dev of S)\nbegin\n"
    "from every e1=S[v > 50.0] -> e2=S[v > e1.v]<3:> -> e3=S[v < e1.v] "
    "within 60000\n"
    "select e1.v as v1, e2[0].v as first, e2[last].v as peak, e3.v as back "
    "insert into Alerts;\nend;\n")


def stage_s3b(cfg, seed, platform, warnings, keep):
    """The served partition branch: S3's app text with an ``@device`` line,
    S3's oracle prefix as columnar chunks, S3's interpreter rows; then the
    Kleene-closure block (the scan kernel under the same bridge) on the
    head of the same stream, against the interpreter."""
    import numpy as np

    if "oracle_rows" not in keep:
        return ["S3 did not run, nothing to compare with"], {}
    events = keep["events"][:cfg["s3_oracle"]]
    chunk = 8192
    devs = np.array([e[0] for e in events], dtype=object)
    vs = np.array([e[1] for e in events], dtype=np.float64)
    ts = np.array([e[2] for e in events], dtype=np.int64)
    ann = (f"@device(strict='true', async='true', "
           f"batch='{cfg['s3b_batch']}', slots='{cfg['s3_slots']}', "
           f"lanes='{cfg['s3_lanes']}')")

    def served(app, n, kernel, facts):
        """``app`` with the ``@device`` line on the first ``n`` events:
        (rows, failures)."""
        def feed(rt):
            ih = rt.input_handler("S")
            for s in range(0, n, chunk):
                e = min(s + chunk, n)
                ih.send_columns({"dev": devs[s:e], "v": vs[s:e]}, ts[s:e])

        def check(rt):
            bad = served_failures(rt, n, platform)
            if not bad:
                bridge = rt.device_bridges[0]
                facts.update(kind=bridge.kind, steps=bridge.probe.steps,
                             first_step_s=round(
                                 bridge.probe.first_step_seconds, 2),
                             flush_causes=dict(bridge.probe.flush_causes),
                             lanes=dict(bridge.runtime.lane_gauges),
                             kernel=bridge.runtime.kernel)
                if bridge.kind != "partition" or bridge.driver is None:
                    bad.append(f"bridge kind '{bridge.kind}', driver "
                               f"{bridge.driver}: not the served partition")
                if bridge.runtime.kernel != kernel:
                    bad.append(f"the lanes step the "
                               f"{bridge.runtime.kernel} kernel, not the "
                               f"{kernel} one")
            return bad

        warnings.drain()
        rows, bad = run_app(app.replace("begin\n", "begin\n" + ann + "\n"),
                            "Alerts", feed, check)
        return rows, bad + [f"logged: {w}" for w in warnings.drain()], feed

    facts = {"events": len(events)}
    rows, bad, _ = served(S3_APP, len(events), "blocked", facts)
    differ = rows_failures(keep["oracle_rows"], rows, ordered=False)
    facts.update(rows=len(rows), rows_equal=not differ)

    kleene = {"events": min(cfg["s3b_kleene_events"], len(events))}
    k_rows, k_bad, feed = served(S3B_KLEENE_APP, kleene["events"], "scan",
                                 kleene)
    ref, ref_bad = run_app(S3B_KLEENE_APP, "Alerts", feed,
                           reference_failures)
    k_differ = rows_failures(ref, k_rows, ordered=False)
    kleene.update(rows=len(k_rows), rows_equal=not k_differ)
    if len(ref) < 50:
        k_bad.append(f"{len(ref)} interpreter rows: the comparison would "
                     f"prove little")
    facts["kleene"] = kleene
    return bad + differ + [f"kleene: {b}"
                           for b in k_bad + ref_bad + k_differ], facts


def stage_s4(cfg, seed, platform, warnings, keep):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if len(jax.devices()) < 4:
        return None, {"skipped": f"{len(jax.devices())} device"}
    if "rows" not in keep:
        return ["S3 did not run, nothing to compare with"], {}
    devices = jax.devices()[:4]
    warnings.drain()
    _, rows, rt, facts = run_flagship(
        cfg, keep["events"], mesh=Mesh(np.array(devices), ("p",)))
    bad = [f"logged: {w}" for w in warnings.drain()]
    bad += off_platform(rt.state, platform)
    per_device = cfg["s3_lanes"] // 4
    for path, leaf in jax.tree_util.tree_leaves_with_path(rt.state):
        name = jax.tree_util.keystr(path)
        if leaf.sharding.device_set != set(devices):
            bad.append(f"state{name} is on {len(leaf.sharding.device_set)} "
                       f"devices, not the mesh's four")
        lanes = sorted(s.data.shape[0] for s in leaf.addressable_shards)
        if lanes != [per_device] * 4:
            bad.append(f"state{name} shards hold {lanes} lanes, want "
                       f"{per_device} each")
    if facts["drops"]:
        bad.append(f"drops == {facts['drops']}")
    same = rows_failures(keep["rows"], rows, ordered=False)
    bad += [f"vs S3: {f}" for f in same]
    facts.update(devices=[str(d) for d in devices],
                 lanes_per_device=per_device, rows_equal_s3=not same)
    return bad, facts


# ---------------------------------------------------------------------------
# S5 — compile sweep: every kind the device compilers accept
# ---------------------------------------------------------------------------

_S = "define stream S (sym string, price double, vol long, ets long);\n"
_ABC = ("define stream A (v long);\ndefine stream B (v long);\n"
        "define stream C (v long);\n")
_TWO = ("define stream Bid (sym string, price double);\n"
        "define stream Ask (sym string, price double);\n")

# Fourteen of the fifteen window names the compiler accepts are here; the
# fifteenth, the pass-through ``#window()``, has no spelling the parser turns
# into a Window handler and compiles as the unwindowed path anyway.
# (kind, feed, app text). Feeds: "S" one stream with an event-time attribute,
# "S-chunks" the same in chunks of one device batch (batch() is defined by
# the chunk), "ABC" three interleaved streams, "two" a two-sided join feed,
# "agg" an incremental aggregation read back with an on-demand query.
S5_CASES = [
    ("filter+projection", "S", _S + """{device}
from S[price > 10.0 and vol < 90] select sym, price * 2.0 as p2, vol + 1 as v1
insert into O;"""),
    ("group-by running", "S", _S + """{device}
from S select sym, sum(price) as total, count() as c, avg(price) as ap
group by sym insert into O;"""),
    ("group-by two keys (splitmix64 buckets)", "S", _S + """{device}
from S[vol < 8] select sym, vol, sum(price) as total, count() as c
group by sym, vol insert into O;"""),
    ("window.length", "S", _S + """{device}
from S[price > 10.0]#window.length(10)
select sym, sum(price) as total, count() as c, avg(price) as ap,
       min(vol) as lo, max(vol) as hi insert into O;"""),
    ("window.length group-by", "S", _S + """{device}
from S#window.length(10) select sym, sum(price) as total, count() as c
group by sym insert into O;"""),
    ("window.lengthBatch", "S", _S + """{device}
from S#window.lengthBatch(5) select sum(vol) as s, count() as c
insert into O;"""),
    ("window.time", "S", _S + """{device}
from S#window.time(200) select sym, sum(vol) as s, count() as c
insert into O;"""),
    ("window.externalTime", "S", _S + """{device}
from S#window.externalTime(ets, 200)
select sym, sum(price) as total, count() as c insert into O;"""),
    ("window.timeBatch", "S", _S + """{device}
from S#window.timeBatch(1 sec)
select sym, sum(price) as total, count() as c, avg(price) as ap,
       min(price) as lo insert into O;"""),
    ("window.externalTimeBatch", "S", _S + """{device}
from S#window.externalTimeBatch(ets, 500)
select sym, sum(price) as total, count() as c insert into O;"""),
    ("window.timeLength", "S", _S + """{device}
from S#window.timeLength(1 sec, 5)
select sym, sum(price) as total, count() as c, min(price) as lo
insert into O;"""),
    ("window.delay", "S", _S + """{device}
from S#window.delay(500) select sym, price insert into O;"""),
    ("window.session", "S", _S + """{device}
from S#window.session(100)
select sym, sum(price) as total, count() as c, max(vol) as hv
insert into O;"""),
    ("window.batch", "S-chunks", _S + """{device}
from S#window.batch() select sum(vol) as s, count() as c insert into O;"""),
    ("window.sort", "S", _S + """{device}
from S#window.sort(5, price)
select sym, sum(price) as total, count() as c, min(price) as lo
insert into O;"""),
    ("window.frequent", "S", _S + """{device}
from S#window.frequent(3, sym)
select sym, vol, sum(vol) as s, count() as c, avg(vol) as a insert into O;"""),
    ("window.lossyFrequent", "S", _S + """{device}
from S#window.lossyFrequent(0.3, 0.05, sym)
select sym, vol, sum(vol) as s, count() as c insert into O;"""),
    ("window.hopping", "S", _S + """{device}
from S#window.hopping(1 sec, 400)
select sum(price) as total, count() as c, max(price) as hi insert into O;"""),
    ("window.hopping group-by + order by / limit", "S", _S + """{device}
from S#window.hopping(1 sec, 400)
select sym, sum(vol) as s, count() as c, max(price) as hi
group by sym order by c desc, s limit 2 insert into O;"""),
    ("stdDev running", "S", _S + """{device}
from S select sym, stdDev(price) as sd, count() as c insert into O;"""),
    ("stdDev window.length", "S", _S + """{device}
from S#window.length(8) select sym, stdDev(price) as sd, sum(price) as total
insert into O;"""),
    ("stdDev window.sort", "S", _S + """{device}
from S#window.sort(5, price) select sym, stdDev(price) as sd insert into O;"""),
    ("join (windowed, two streams)", "two", _TWO + """{device}
from Bid#window.time(2000) join Ask#window.time(3000)
  on Bid.sym == Ask.sym and Ask.price < Bid.price
select Bid.sym as s, Bid.price as bp, Ask.price as ap insert into O;"""),
    ("pattern: stream chain (blocked kernel)", "ABC", _ABC + """{device}
from every e1=A[v > 5] -> e2=B[v > e1.v] -> e3=C[v > e2.v] within 500
select e1.v as a, e2.v as b, e3.v as c insert into O;"""),
    ("sequence (blocked kernel)", "ABC", _ABC + """{device}
from every e1=A[v > 5], e2=B[v > e1.v]
select e1.v as a, e2.v as b insert into O;"""),
    ("pattern: count state (scan kernel)", "ABC", _ABC + """{device}
from every e1=A[v > 10] -> e2=B[v > 5]<2:3> -> e3=C[v > e1.v]
select e1.v as a, e2[0].v as b0, e2[last].v as bl, e3.v as c
insert into O;"""),
    ("pattern: logical state (scan kernel)", "ABC", _ABC + """{device}
from every e1=A[v > 0] -> e2=B[v > 10] and e3=C[v > 20]
select e1.v as a, e2.v as b, e3.v as c insert into O;"""),
    ("pattern: absent state (scan kernel)", "ABC", _ABC + """{device}
from every e1=A[v > 0] -> not B for 100 -> e3=C[v > 0]
select e1.v as a, e3.v as c insert into O;"""),
    ("incremental aggregation", "agg",
     "define stream S (sym string, price double, vol long, ets long);\n"
     """{device}
define aggregation Agg
from S select sym, sum(price) as total, count() as c, avg(price) as ap,
       min(vol) as lo, max(vol) as hi, stdDev(price) as sd
group by sym aggregate every sec...year;"""),
]
S5_BATCH = 64
S5_EVENTS = 3 * S5_BATCH


def _s5_feed(kind: str, seed: int):
    """(feed(rt), events sent per stream)."""
    import random
    rng = random.Random(seed + 5)
    n = S5_EVENTS
    if kind in ("S", "S-chunks", "agg"):
        evs, ts = [], 1_700_000_000_000
        for _ in range(n):
            ts += rng.choice([1, 2, 5, 30, 120])
            evs.append(([rng.choice("abcd"), rng.randrange(401) / 4.0,
                         rng.randrange(100), ts], ts))

        def feed(rt):
            ih = rt.input_handler("S")
            if kind == "S-chunks":
                from siddhi_tpu.core.event import Event
                for s in range(0, n, S5_BATCH):
                    ih.send([Event(t, list(r))
                             for r, t in evs[s:s + S5_BATCH]])
            else:
                for row, t in evs:
                    ih.send(list(row), timestamp=t)
        return feed, {"S": n}
    if kind == "ABC":
        evs, ts = [], 1000
        for _ in range(n):
            ts += rng.choice([10, 30, 60, 150])
            evs.append((rng.choice("ABC"), [rng.randrange(40)], ts))
    else:                                               # "two"
        evs = [(rng.choice(["Bid", "Ask"]),
                [rng.choice("abc"), rng.randrange(4, 200) / 4.0],
                1000 + i * 100) for i in range(n)]

    def feed(rt):
        for sid, row, t in evs:
            rt.input_handler(sid).send(list(row), timestamp=t)
    return feed, collections.Counter(sid for sid, _, _ in evs)


def _run_aggregation(app: str, feed):
    """(rows of the per-second rollup, whether the device reducer ran)."""
    from siddhi_tpu import SiddhiManager

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app, playback=True)
        rt.start()
        feed(rt)
        engaged = rt.ctx.aggregations["Agg"]._dev is not None
        rows = rt.query("from Agg within 0L, 9999999999999L per 'seconds' "
                        "select AGG_TIMESTAMP, sym, total, c, ap, lo, hi, sd")
        return sorted(tuple(e.data) for e in rows), engaged
    finally:
        m.shutdown()


def stage_s5(cfg, seed, platform, warnings, keep):
    ann = f"@device(strict='true', batch='{S5_BATCH}')"
    failed, t_all = [], time.perf_counter()
    for kind, feed_kind, app in S5_CASES:
        feed, sent = _s5_feed(feed_kind, seed)
        t0 = time.perf_counter()
        warnings.drain()
        try:
            if feed_kind == "agg":
                dev, engaged = _run_aggregation(app.format(device=ann), feed)
                ref, _ = _run_aggregation(app.format(device=""), feed)
                bad = rows_failures(ref, dev, ordered=True)
                if not engaged:
                    bad.append("the device reducer was not engaged")
                bad += [f"logged: {w}" for w in warnings.drain()]
                facts = {"rows": len(dev), "first_step_s": None}
            else:
                # one stream emits in arrival order on both engines; rows
                # a pattern or join emits on one event may swap places
                bad, facts = run_pair(app, ann, "O", feed, sent, platform,
                                      feed_kind.startswith("S"), warnings)
        except Exception as e:  # noqa: BLE001 — one kind the chip's compiler
            # refuses must not hide what it does to the kinds after it
            bad = [f"{type(e).__name__}: {str(e)[:600]}"]
            facts = {"rows": 0, "first_step_s": None}
        verdict = "FAILED" if bad else "ok"
        say(f"S5 {kind}: {verdict} first_step_s={facts.get('first_step_s')} "
            f"rows={facts.get('rows')} "
            f"total_s={time.perf_counter() - t0:.1f}")
        for b in bad:
            say(f"S5 {kind}:   {b}")
        if bad:
            failed.append(kind)
    return ([f"{len(failed)} kind(s) failed: {failed}"] if failed else []), {
        "kinds": len(S5_CASES), "failed": failed,
        "total_s": round(time.perf_counter() - t_all, 1)}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# S6 — served, grouped hopping flush + order by / limit (NEXmark Query 5)
# ---------------------------------------------------------------------------

S6_APP = """
define stream Bid (auction long, bidder long, price long);
{device}
from Bid#window.hopping(20000, 4000)
select auction, count() as num
group by auction
order by num desc
limit 1
insert into HotItems;
"""


def stage_s6(cfg, seed, platform, warnings, keep):
    import numpy as np

    n, chunk = cfg["s6_events"], 8192
    rng = np.random.default_rng(seed + 6)
    # half the bids on a hot auction that moves on every 1,536 bids, ids
    # past 32 bits: a key folded to its low word would meet another
    auction = ((rng.zipf(1.7, n) - 1 + np.arange(n) // 1536 * 100) % 4096
               + 2 ** 33).astype(np.int64)
    cols = {"auction": auction,
            "bidder": rng.integers(0, 100_000, n).astype(np.int64),
            "price": rng.integers(100, 1_000_000, n).astype(np.int64)}
    ts = 1_000_000 + np.arange(n, dtype=np.int64)

    def feed(rt):
        ih = rt.input_handler("Bid")
        for s in range(0, n, chunk):
            ih.send_columns({k: v[s:s + chunk] for k, v in cols.items()},
                            ts[s:s + chunk])

    def also(rt):
        gauges = rt.device_bridges[0].runtime.step_gauges
        live, moves = gauges["window_live_keys"], gauges["compact_moves"]
        # no filter: the batch's `valid` is a prefix and nothing is moved
        return ([] if live > 100 else [f"window_live_keys reads {live}"]) \
            + ([] if moves == 0 else [f"compact_moves reads {moves}"])

    ann = "@device(strict='true', async='true', batch='2048', window='22528')"
    bad, facts = run_pair(S6_APP, ann, "HotItems", feed, {"Bid": n},
                          platform, True, warnings, also=also)
    if facts.get("rows") != (n - 1) // 4000:
        bad.append(f"{facts.get('rows')} rows, a boundary every 4,000 of {n} "
                   f"events makes {(n - 1) // 4000}")
    return bad, facts


# ---------------------------------------------------------------------------
# S7 — served, keyed sliding window (the guide's value partition)
# ---------------------------------------------------------------------------

S7_APP = """
define stream TempStream (deviceID long, roomNo int, temp double);
partition with (deviceID of TempStream) begin
{device}
from TempStream#window.length(10)
select roomNo, deviceID, max(temp) as maxTemp
having maxTemp > 99.9
insert into DeviceTempStream;
end;
"""


def stage_s7(cfg, seed, platform, warnings, keep):
    import numpy as np

    n, chunk = cfg["s7_events"], 8192
    rng = np.random.default_rng(seed + 7)
    p = np.arange(1, 4097, dtype=np.float64) ** -0.6
    cols = {"deviceID": (rng.choice(4096, size=n, p=p / p.sum())
                         * 1_000_003 + 2 ** 40).astype(np.int64),
            "roomNo": rng.integers(0, 1000, n).astype(np.int32),
            "temp": np.round(rng.uniform(0.0, 100.0, n), 3)}
    ts = 1_000_000 + np.arange(n, dtype=np.int64)

    def feed(rt):
        ih = rt.input_handler("TempStream")
        for s in range(0, n, chunk):
            ih.send_columns({k: v[s:s + chunk] for k, v in cols.items()},
                            ts[s:s + chunk])

    def also(rt):
        gauges = rt.device_bridges[0].runtime.step_gauges
        facts.update(gauges)
        live = gauges["keyed_live_keys"]
        want = len(np.unique(cols["deviceID"]))
        return [] if live == want else \
            [f"keyed_live_keys reads {live}, the stream holds {want} ids"]

    facts: dict = {}
    ann = "@device(strict='true', async='true', batch='2048', keys='8192')"
    bad, got = run_pair(S7_APP, ann, "DeviceTempStream", feed,
                        {"TempStream": n}, platform, False, warnings,
                        also=also)
    facts.update(got)
    if facts.get("rows", 0) < n // 400:
        bad.append(f"{facts.get('rows')} rows of {n} readings: the stage "
                   f"checks too little")
    return bad, facts


STAGES = {"S1": stage_s1, "S2": stage_s2, "S3": stage_s3, "S3B": stage_s3b,
          "S4": stage_s4, "S5": stage_s5, "S6": stage_s6, "S7": stage_s7}


def main(argv=None) -> int:
    global _prefix
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="reduced size on whatever platform JAX finds; "
                         "every line says so and no result is printed")
    ap.add_argument("--only", default="",
                    help="comma-separated stages to run (a partial run "
                         "never passes)")
    args = ap.parse_args(argv)
    only = [s.strip().upper() for s in args.only.split(",") if s.strip()]
    if any(s not in STAGES for s in only):
        ap.error(f"--only takes stages of {list(STAGES)}")
    cfg = REHEARSAL if args.rehearsal else FULL

    import jax
    import jaxlib

    dev = jax.devices()[0]
    platform, kind, count = dev.platform, dev.device_kind, len(jax.devices())
    if args.rehearsal:
        _prefix = f"[REHEARSAL reduced size on {platform} - not the chip check] "
    if platform != "tpu" and not args.rehearsal:
        print(f"chip_smoke: FAILED at stage device: JAX found platform "
              f"'{platform}' ({kind} x{count}), not a TPU", file=sys.stderr)
        return 1
    try:
        from siddhi_tpu.tpu.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAILED at stage import: {e} (run it from the "
              f"root of a siddhi_tpu checkout)", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string, never fatal
        libtpu = "not installed"
    say(f"platform: {platform}")
    say(f"device_kind: {kind}")
    say(f"device_count: {count}")
    say(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}")
    say(f"compile_cache_dir: {cache_dir}")
    say(f"seed: {args.seed}")

    warnings = _Warnings()
    logging.getLogger("siddhi_tpu").addHandler(warnings)
    keep: dict = {}         # S3's events and rows, for S4 to compare with
    failed = []
    for name, stage in STAGES.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            bad, facts = stage(cfg, args.seed, platform, warnings, keep)
        except Exception as e:  # noqa: BLE001 — report the stage, run the
            # rest: a chip call is too dear to learn one failure at a time
            import traceback
            traceback.print_exc()
            bad, facts = [f"{type(e).__name__}: {str(e)[:600]}"], {}
        verdict = "skipped" if bad is None else "FAILED" if bad else "ok"
        say(f"{name}: {verdict} {json.dumps(facts)} "
            f"stage_s={time.perf_counter() - t0:.1f}")
        for b in bad or []:
            say(f"{name}:   {b}")
        if bad:
            failed.append(name)

    if failed:
        say(f"FAILED at stage(s): {', '.join(failed)}")
        return 1
    if only:
        say(f"partial run ({','.join(only)}): passed what it ran, which is "
            f"not the chip check")
        return 3
    if args.rehearsal:
        say("rehearsal passed (no result line: this was not the chip check)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
