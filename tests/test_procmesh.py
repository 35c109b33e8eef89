"""procmesh: process-per-host mesh runtime + socket control plane (ISSUE 16).

The acceptance pins:

- a process-mode fabric is byte-compatible with the in-process fabric —
  deploy/ingest/flush/live-migration produce identical event streams;
- real-kill chaos: SIGKILL a worker process mid-ingest, the supervisor
  restarts it, the fabric replays the spill — the killed tenant AND its
  neighbours stay byte-identical to solo oracles (exactly-once);
- a lost-ack retry of the same seq-stamped ingest op applies nothing and
  re-ships the same outbox tail (the ``K_ADOPT`` discipline over the
  control socket);
- a worker that can never boot exhausts its restart budget and the
  supervisor gives up on it (record-before-actuate, on the flight
  recorder) instead of storming forever;
- ``@app:host_batch(workers.mode='process')`` routes partition lanes
  through a process lane pool, byte-identical to sequential and threaded
  runs, including a mid-stream snapshot/restore through the pool;
- ``close()`` tears down every ``procmesh.*`` and per-child scraped
  gauge — no zombie families after the fleet is gone.
"""

import os
import random
import time

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.mesh import MeshConfig, MeshFabric
from siddhi_tpu.procmesh import WorkerDown

APP = """
@app:name('t{i}')
define stream S (dev string, v double);
@info(name='q{i}')
from S[v > 1.0] select dev, v insert into Out;
"""


def _chunks(n_chunks: int = 12, width: int = 4):
    out = []
    for c in range(n_chunks):
        rows = [[f"d{c}_{j}", float(c + j)] for j in range(width)]
        ts = [c * 10 + j + 1 for j in range(width)]
        out.append((rows, ts))
    return out


def _solo_oracle(i: int, chunks) -> list:
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(APP.format(i=i), playback=True)
        out = []
        rt.add_callback("Out", StreamCallback(
            lambda evs: out.extend(tuple(e.data) for e in evs)))
        rt.start()
        ih = rt.input_handler("S")
        for c, t in chunks:
            ih.send_rows([list(r) for r in c], list(t))
        return out
    finally:
        m.shutdown()


def _proc_cfg(**kw) -> MeshConfig:
    kw.setdefault("mode", "process")
    kw.setdefault("snapshot_every_chunks", 1)
    kw.setdefault("heartbeat_interval_s", 0.2)
    kw.setdefault("capacity_per_host", 4)
    return MeshConfig(**kw)


def _run_fabric(tmp_path, mode: str, chunks, migrate_mid: bool):
    """Deploy 2 tenants, feed, optionally live-migrate t0 mid-stream."""
    got = {0: [], 1: []}
    cfg = (_proc_cfg() if mode == "process" else
           MeshConfig(snapshot_every_chunks=1, capacity_per_host=4))
    fab = MeshFabric(2, str(tmp_path / f"m-{mode}"), config=cfg)
    try:
        fab.add_tenants([APP.format(i=i) for i in range(2)])
        for i in range(2):
            fab.add_callback(f"t{i}", "Out",
                             lambda evs, i=i: got[i].extend(
                                 tuple(e.data) for e in evs))
        for c, (rows, ts) in enumerate(chunks):
            if migrate_mid and c == len(chunks) // 2:
                st = fab.tenants["t0"]
                assert fab.migrate("t0", 1 - st.host)
            for i in range(2):
                fab.send(f"t{i}", "S", rows, ts)
        fab.flush()
        rep = fab.report()
        assert rep["mode"] == mode
        return got, rep
    finally:
        fab.close()


# -- byte-compat with the in-process fabric -----------------------------------

def test_process_mode_parity_with_inproc(tmp_path):
    chunks = _chunks(8)
    a, _ = _run_fabric(tmp_path, "inproc", chunks, migrate_mid=False)
    b, repb = _run_fabric(tmp_path, "process", chunks, migrate_mid=False)
    assert a == b
    assert a[0] == _solo_oracle(0, chunks)
    assert repb["supervisor"] is not None


def test_process_mode_live_migration_parity(tmp_path):
    """A live migration over the control socket (snapshot → restore →
    adopt on another OS process) matches the in-process move byte for
    byte."""
    chunks = _chunks(8)
    a, repa = _run_fabric(tmp_path, "inproc", chunks, migrate_mid=True)
    b, repb = _run_fabric(tmp_path, "process", chunks, migrate_mid=True)
    assert a == b
    assert repa["migrations"] == repb["migrations"] == 1


# -- real-kill chaos ----------------------------------------------------------

def test_sigkill_mid_ingest_exactly_once(tmp_path):
    """SIGKILL the worker process that hosts t0 mid-stream. The supervisor
    restarts it from the real process table (poll() evidence, not a
    simulated flag); the fabric replays the spill through the child-side
    seq dedup — both tenants byte-identical to solo oracles."""
    chunks = _chunks(12)
    oracle = {i: _solo_oracle(i, chunks) for i in range(2)}
    got = {0: [], 1: []}
    fab = MeshFabric(2, str(tmp_path / "m"), config=_proc_cfg())
    try:
        fab.add_tenants([APP.format(i=i) for i in range(2)])
        for i in range(2):
            fab.add_callback(f"t{i}", "Out",
                             lambda evs, i=i: got[i].extend(
                                 tuple(e.data) for e in evs))
        victim = fab.tenants["t0"].host
        pid = fab.supervisor.handles[victim].pid
        for c, (rows, ts) in enumerate(chunks):
            if c == 5:
                fab.kill_host(victim)          # real SIGKILL, real process
            for i in range(2):
                fab.send(f"t{i}", "S", rows, ts)
            time.sleep(0.02)
        deadline = time.time() + 30
        while time.time() < deadline:
            rep = fab.report()
            if all(h["alive"] for h in rep["hosts"].values()) \
                    and not rep["spill_backlog"]:
                break
            time.sleep(0.2)
        fab.flush()
        rep = fab.report()
        assert all(h["alive"] for h in rep["hosts"].values())
        assert rep["supervisor"]["workers"][victim]["restarts"] >= 1
        assert fab.supervisor.handles[victim].pid != pid  # a NEW process
        assert rep["dup_chunks"] == 0
        # the worker_down evidence landed before the restart decision
        kinds = [e["kind"] for e in fab.flight.export(category="procmesh")]
        assert "worker_down" in kinds and "decision:restart_worker" in kinds
        assert kinds.index("worker_down") \
            < kinds.index("decision:restart_worker")
    finally:
        fab.close()
    assert got[0] == oracle[0]
    assert got[1] == oracle[1]


def test_ingest_retry_idempotent(tmp_path):
    """A lost-ack retry (same seq, same ack cursor) applies nothing and
    re-ships the identical outbox tail."""
    fab = MeshFabric(1, str(tmp_path / "m"), config=_proc_cfg())
    try:
        fab.add_tenants([APP.format(i=0)])
        fab.add_callback("t0", "Out", lambda evs: None)  # arm the outbox
        rt = fab.hosts[fab.tenants["t0"].host].runtimes["t0"]
        h = {"tenant": "t0", "stream": "S", "seq": 1, "ack": -1,
             "rows": [["a", 5.0], ["b", 0.5]], "ts": [1, 2]}
        first, _ = rt.client.call("ingest", dict(h))
        retry, _ = rt.client.call("ingest", dict(h))   # the lost-ack replay
        assert first["applied"] is True
        assert retry["applied"] is False               # dedup'd, not re-run
        assert retry["events"] == first["events"]      # same outbox tail
        assert len(first["events"]) == 1               # only v>1.0 matched
        # acking past the tail stops re-shipping
        h["seq"], h["ack"] = 2, first["events"][-1][0]
        h["rows"], h["ts"] = [["c", 9.0]], [3]
        nxt, _ = rt.client.call("ingest", dict(h))
        assert all(e[0] > h["ack"] for e in nxt["events"])
    finally:
        fab.close()


def test_restart_storm_gives_up(tmp_path):
    """A worker that can never boot again must exhaust its restart budget
    and be given up on — decision on the flight recorder — rather than
    fork-storming forever."""
    fab = MeshFabric(1, str(tmp_path / "m"), config=_proc_cfg(
        restart_max=2, restart_base_s=0.05, heartbeat_interval_s=0.1))
    try:
        fab.add_tenants([APP.format(i=0)])
        fab.send("t0", "S", [["a", 5.0]], [1])
        fab.flush()
        # every respawn from here on dies at boot (exit 3)
        fab.supervisor.cfg.env["SIDDHI_PROCMESH_CRASH_ON_BOOT"] = "1"
        fab.kill_host(0)
        deadline = time.time() + 30
        while time.time() < deadline:
            w = fab.report()["supervisor"]["workers"][0]
            if w["gave_up"]:
                break
            time.sleep(0.2)
        assert w["gave_up"]
        assert not w["alive"]
        kinds = [e["kind"] for e in fab.flight.export(category="procmesh")]
        assert "decision:give_up" in kinds
        # the dead shard shows (not silently healthy); sends spill
        assert not fab.report()["hosts"][0]["alive"]
        fab.send("t0", "S", [["b", 6.0]], [2])
        assert fab.report()["spill_backlog"].get("t0")
    finally:
        fab.close()


def test_connect_to_dead_port_raises_worker_down():
    import socket as s
    from siddhi_tpu.procmesh import protocol
    srv = s.socket()
    srv.bind(("127.0.0.1", 0))
    port = srv.getsockname()[1]
    srv.close()                        # nothing listens here any more
    with pytest.raises(WorkerDown):
        protocol.connect(port, timeout=1.0)


# -- process lane pool (@app:host_batch workers.mode) -------------------------

_PAR_APP = """
@app(name='%s')
@app:host_batch(batch='2048', lanes='8', workers='%d'%s)
define stream S (dev string, v double);
partition with (dev of S)
begin
from every e1=S[v > 70.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v] within 400
select e1.v as v1, e2.v as v2, e3.v as v3 insert into Alerts;
end;
"""


def _pattern_feed(n=2000, seed=13):
    rng = random.Random(seed)
    return [(f"dev{rng.randrange(8)}", round(rng.uniform(0, 100), 3),
             1_000 + i) for i in range(n)]


def _run_pattern(workers, mode, feed, name, snapshot_at=None,
                 restore_blob=None):
    extra = f", workers.mode='{mode}'" if mode else ""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(_PAR_APP % (name, workers, extra),
                                         playback=True)
        got = []
        rt.add_callback("Alerts", StreamCallback(
            lambda evs: got.extend(tuple(e.data) for e in evs)))
        rt.start()
        if restore_blob is not None:
            rt.restore(restore_blob)
        ih = rt.input_handler("S")
        devs = np.empty(len(feed), dtype=object)
        devs[:] = [d for d, _, _ in feed]
        vals = np.asarray([v for _, v, _ in feed])
        tss = np.asarray([t for _, _, t in feed], np.int64)
        blob = None
        for s in range(0, len(feed), 512):
            ih.send_columns({"dev": devs[s:s + 512], "v": vals[s:s + 512]},
                            tss[s:s + 512])
            if snapshot_at is not None and s + 512 >= snapshot_at \
                    and blob is None:
                blob = rt.snapshot()
        rt.flush_host()
        matches = rt.host_bridges[0].runtime.prt.match_count
        return got, matches, blob
    finally:
        m.shutdown()


def test_lane_pool_parity_and_snapshot(tmp_path):
    """workers.mode='process' is byte-identical to sequential AND threaded
    lanes; a snapshot cut through the pool restores into a fresh pool and
    continues byte-identically."""
    feed = _pattern_feed()
    seq, m1, _ = _run_pattern(1, None, feed, "lp-seq")
    thr, m2, _ = _run_pattern(2, None, feed, "lp-thr")
    prc, m3, _ = _run_pattern(2, "process", feed, "lp-proc")
    assert m1 > 0, "corpus produced no matches"
    assert seq == thr == prc
    assert m1 == m2 == m3
    cut = 1024
    ga, _x, blob = _run_pattern(2, "process", feed[:cut], "lp-a",
                                snapshot_at=cut)
    assert blob is not None
    gb, _y, _ = _run_pattern(2, "process", feed[cut:], "lp-b",
                             restore_blob=blob)
    assert ga + gb == seq


def test_lane_pool_rejects_bad_mode():
    m = SiddhiManager()
    try:
        with pytest.raises(ValueError):
            m.create_siddhi_app_runtime(
                _PAR_APP % ("lp-bad", 2, ", workers.mode='rdma'"),
                playback=True)
    finally:
        m.shutdown()


# -- elasticity + metrics teardown --------------------------------------------

def test_process_mode_fixed_fleet(tmp_path):
    fab = MeshFabric(1, str(tmp_path / "m"), config=_proc_cfg())
    try:
        with pytest.raises(ValueError):
            fab.add_host(capacity=4)
        with pytest.raises(ValueError):
            fab.remove_host(0)
    finally:
        fab.close()


def test_fabric_parent_claims_no_device_and_pins_workers_to_cpu(tmp_path):
    """One process per chip: on a chip host (no JAX_PLATFORMS set) a
    process-mode fabric parent must initialise no JAX backend — a parent that
    has touched JAX holds the chip — and its workers, which run the NumPy
    tiers only, are spawned pinned to the CPU backend. Runs in a fresh
    interpreter: in this one a backend is long since up."""
    import json
    import subprocess
    import sys
    script = """
import json, os, subprocess, sys
os.environ.pop("JAX_PLATFORMS", None)
real, envs = subprocess.Popen, []
def spy(*a, **kw):
    envs.append(kw["env"].get("JAX_PLATFORMS"))
    return real(*a, **kw)
subprocess.Popen = spy
from siddhi_tpu.mesh import MeshConfig, MeshFabric
fab = MeshFabric(1, sys.argv[1], config=MeshConfig(
    mode="process", heartbeat_interval_s=0.2, capacity_per_host=4))
try:
    from jax._src import xla_bridge
    print(json.dumps({"backend_up": xla_bridge.backends_are_initialized(),
                      "devices": [h.device for h in fab.hosts.values()],
                      "worker_platforms": envs}))
finally:
    fab.close()
"""
    from siddhi_tpu.procmesh.protocol import child_env
    p = subprocess.run([sys.executable, "-c", script, str(tmp_path / "m")],
                       capture_output=True, text=True, timeout=120,
                       env=child_env())
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"backend_up": False, "devices": [None],
                   "worker_platforms": ["cpu"]}


def test_procmesh_metrics_register_and_teardown(tmp_path):
    """procmesh.* worker gauges and the scraped per-child mesh.h{i}.child.*
    families render while the fleet lives and unregister on close() — no
    zombie gauges from dead processes."""
    from siddhi_tpu.observability import render
    fab = MeshFabric(2, str(tmp_path / "m"), config=_proc_cfg())
    m = SiddhiManager()
    try:
        fab.add_tenants([APP.format(i=0)])
        rt = m.create_siddhi_app_runtime(
            "@app(name='obs')\ndefine stream S (v double);\n"
            "from S select v insert into O;", playback=True)
        rt.start()
        sm = rt.ctx.statistics_manager
        fab.register_metrics(sm)
        fab.send("t0", "S", [["a", 5.0]], [1])
        fab.flush()
        fab.sync_children()
        snap = sm.snapshot_trackers()
        keys = [k for d in snap.values() for k in d]
        assert any(k.startswith("procmesh.w0.") for k in keys)
        assert any(k == "mesh.self.process_mode" for k in keys)
        assert any(k.startswith("mesh.h0.child.") for k in keys), keys
        text = render([sm])
        assert "siddhi_tpu_procmesh_" in text
        fab.close()
        snap = sm.snapshot_trackers()
        keys = [k for d in snap.values() for k in d]
        assert not any(k.startswith(("mesh.", "procmesh.")) for k in keys)
        assert "siddhi_tpu_procmesh_" not in render([sm])
    finally:
        fab.close()
        m.shutdown()


def test_worker_flight_entries_absorbed(tmp_path):
    """Child-side flight entries surface on the fabric recorder with the
    ``h{i}:`` site prefix (one mesh-wide timeline)."""
    fab = MeshFabric(1, str(tmp_path / "m"), config=_proc_cfg())
    try:
        fab.add_tenants([APP.format(i=0)])
        fab.send("t0", "S", [["a", 5.0]], [1])
        fab.flush()
        fab.sync_children()
        sites = [e["site"] for e in fab.flight.export()
                 if e["site"].startswith("h0:")]
        assert sites, "no child flight entries were absorbed"
    finally:
        fab.close()
