"""Multi-host fault tolerance for the DCN shard layer (ISSUE 4).

Exactly-once across every failure shape the guard covers: lost acks
(chaos ``dcn.drop.p`` → retry + receiver dedup), killed serving connections
(``dcn.kill.p`` → reconnect), dead peers (spill → in-order replay on
recovery), a peer process SIGKILLed mid-ingest and restarted (snapshot
restore + spill replay, two real OS processes), and full failover (survivor
adopts the dead host's lane group from the global-lane-keyed snapshot
revision, then hands it back via K_ADOPT when the host returns). Every
scenario pins match counts against the single-host oracle — zero loss,
zero duplicates.
"""

import importlib.util
import multiprocessing as mp
import os
import socket
import subprocess
import sys
import time

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.resilience.chaos import ChaosInjector, parse_chaos_annotation
from siddhi_tpu.resilience.dcn_guard import (
    PEER_DOWN,
    PEER_HEALTHY,
    PEER_PROBING,
    PEER_SUSPECT,
    DCNGuardConfig,
    LaneGroupSnapshotStore,
    PeerHealth,
    SpillQueue,
)
from siddhi_tpu.tpu.dcn import (
    DCNWorker,
    K_FLUSH,
    K_FLUSHED,
    LaneTopology,
    recv_msg,
    send_msg,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

APP = """
define stream S (dev string, v double);
partition with (dev of S)
begin
from every e1=S[v > 50.0] -> e2=S[v > e1.v]
select e1.v as v1, e2.v as v2 insert into Alerts;
end;
"""


def _events(n=400, keys=12, seed=21):
    import random
    rng = random.Random(seed)
    out = []
    for i in range(n):
        out.append(([f"dev{rng.randrange(keys)}",
                     round(rng.uniform(0.0, 100.0), 2)], 1000 + i))
    return out


def _oracle(events) -> int:
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP, playback=True)
    host = []
    rt.add_callback("Alerts", StreamCallback(lambda evs: host.extend(evs)))
    rt.start()
    ih = rt.input_handler("S")
    for row, ts in events:
        ih.send(list(row), timestamp=ts)
    m.shutdown()
    return len(host)


def _free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _mk_pair(chaos0=None, chaos1=None, cfg0=None, cfg1=None, **kw):
    """Two in-process workers over real sockets, each with its own
    topology view. Returns (w0, w1)."""
    p0, p1 = _free_port(), _free_port()
    w1 = DCNWorker(1, LaneTopology(8, 2), APP, "dev", port=p1,
                   peers={0: ("127.0.0.1", p0)}, chaos=chaos1,
                   guard_config=cfg1, **kw)
    w0 = DCNWorker(0, LaneTopology(8, 2), APP, "dev", port=p0,
                   peers={1: ("127.0.0.1", p1)}, chaos=chaos0,
                   guard_config=cfg0, **kw)
    return w0, w1


def _ingest_chunks(w, events, size=10):
    """Many small ingest calls → many DCN frames (one frame per call per
    lane group), so per-frame fault sites actually roll."""
    for i in range(0, len(events), size):
        chunk = events[i:i + size]
        w.ingest([r for r, _ in chunk], [t for _, t in chunk])


def _close_all(*workers):
    for w in workers:
        try:
            w.close()
        except OSError:
            pass


# -- unit: peer state machine ------------------------------------------------
def test_peer_health_state_machine():
    t = [0.0]
    h = PeerHealth(failure_threshold=3, down_cooldown_s=5.0,
                   clock=lambda: t[0])
    assert h.state == PEER_HEALTHY and h.down_since is None
    h.record_failure()
    assert h.state == PEER_SUSPECT
    h.record_failure()
    h.record_failure()
    assert h.state == PEER_DOWN and h.down_since == 0.0
    # within the cool-down no probe is admitted
    t[0] = 3.0
    assert not h.allow_probe() and h.state == PEER_DOWN
    # past it, exactly one probe flips to PROBING
    t[0] = 6.0
    assert h.allow_probe()
    assert h.state == PEER_PROBING
    assert not h.allow_probe()          # second concurrent probe refused
    # failed probe re-opens but KEEPS the original down_since (the takeover
    # deadline must not reset on every probe)
    h.record_failure()
    assert h.state == PEER_DOWN and h.down_since == 0.0
    t[0] = 12.0
    assert h.allow_probe()
    h.record_success()
    assert h.state == PEER_HEALTHY and h.down_since is None
    # hard evidence (failed hand-back) declares down immediately
    t[0] = 20.0
    h.trip()
    assert h.state == PEER_DOWN and h.down_since == 20.0


def test_circuit_breaker_suspect_and_trip():
    from siddhi_tpu.resilience.circuit import CircuitBreaker, CircuitState
    b = CircuitBreaker(failure_threshold=3, cooldown_s=1.0)
    assert not b.suspect
    b.record_failure()
    assert b.suspect and b.state == CircuitState.CLOSED
    b.trip()
    assert b.state == CircuitState.OPEN and b.open_count == 1
    assert not b.allow()


# -- unit: spill queue policies ----------------------------------------------
def test_spill_queue_policies():
    q = SpillQueue(capacity=2, policy="shed")
    assert q.append(b"a", 3) and q.append(b"b", 4)
    assert not q.append(b"c", 5)            # full: incoming shed
    assert q.shed_frames == 1 and q.shed_rows == 5
    assert q.pop_front() == (b"a", 3)       # FIFO order

    q = SpillQueue(capacity=2, policy="drop_oldest")
    q.append(b"a", 1)
    q.append(b"b", 2)
    q.append(b"c", 3)                       # evicts "a"
    assert q.dropped_oldest_frames == 1 and q.dropped_oldest_rows == 1
    assert q.pop_front() == (b"b", 2)

    q = SpillQueue(capacity=1, policy="block", max_wait_s=0.05)
    q.append(b"a", 1)
    t0 = time.monotonic()
    q.wait_for_space()                      # bounded wait, then force in
    assert time.monotonic() - t0 >= 0.04
    assert q.append(b"b", 1)                # never dropped under BLOCK
    assert q.forced == 1 and len(q) == 2

    # push_front restores replay order after a failed attempt
    item = q.pop_front()
    q.push_front(item)
    assert q.pop_front() == item


def test_topology_wire_byte_bound():
    with pytest.raises(ValueError):
        LaneTopology(512, 256)      # host/group indices travel as one byte
    LaneTopology(510, 255)          # the boundary itself is fine


def test_snapshot_store_prunes_revisions(tmp_path):
    import numpy as np
    store = LaneGroupSnapshotStore(str(tmp_path), keep_revisions=2)
    for i in range(5):
        store.save(0, [0, 1], [np.arange(4)], {0: (0, i)})
    revs = sorted(os.listdir(str(tmp_path / "group_0")))
    assert len(revs) == 2, revs     # only the newest two survive
    assert store.latest(0)["dedup"] == {0: (0, 4)}
    # monotone per-host incarnation counter: a restart without an explicit
    # epoch must never reuse a dead incarnation's sequence space
    assert store.next_epoch(3) == 0
    assert store.next_epoch(3) == 1
    assert store.next_epoch(2) == 0


def test_chaos_dcn_annotation_and_sites():
    inj = parse_chaos_annotation({"seed": "5", "dcn.drop.p": "1.0",
                                  "dcn.kill.p": "1.0", "dcn.delay.ms": "1"})
    assert inj.dcn_drop_p == 1.0 and inj.dcn_kill_p == 1.0
    from siddhi_tpu.resilience.chaos import ChaosFault
    with pytest.raises(ChaosFault):
        inj.on_dcn_send("s")
    with pytest.raises(ChaosFault):
        inj.on_dcn_serve("s")
    inj.on_dcn_ack("s")                      # delay only, never raises
    assert inj.counters["dcn_drops"] == 1
    assert inj.counters["dcn_kills"] == 1
    assert inj.report()["probabilities"]["dcn_drop"] == 1.0


# -- exactly-once under injected transport faults ----------------------------
def test_lost_acks_retry_and_dedup_exactly_once():
    """dcn.drop.p drops the ack AFTER the frame hit the wire: the frame
    applied, the sender retries, the receiver must dedup — exactly-once."""
    chaos = ChaosInjector(seed=7, dcn_drop_p=0.3)
    cfg = DCNGuardConfig(retry_max=10, retry_base_s=0.001,
                         retry_cap_s=0.01, failure_threshold=100)
    w0, w1 = _mk_pair(chaos0=chaos, cfg0=cfg)
    try:
        events = _events(300)
        _ingest_chunks(w0, events)
        w0.flush()
        w1.flush()
        total = w0.match_count + w1.match_count
        assert total == _oracle(events), "loss or duplication under lost acks"
        assert chaos.counters["dcn_drops"] > 0, "chaos site never fired"
        assert w1.dup_frames > 0, "no retry was deduped — site miswired?"
        assert w0.forwarded == w1.received, (
            "forwarded must count acked rows exactly once")
        assert w0.guard.peer_counters[1]["retries"] > 0
    finally:
        _close_all(w0, w1)


def test_killed_connections_reconnect_exactly_once():
    """dcn.kill.p aborts the serving connection BEFORE the frame applies:
    the sender must evict the broken socket, reconnect, and resend."""
    chaos = ChaosInjector(seed=3, dcn_kill_p=0.25, dcn_delay_ms=2)
    cfg = DCNGuardConfig(retry_max=10, retry_base_s=0.001,
                         retry_cap_s=0.01, failure_threshold=100)
    w0, w1 = _mk_pair(chaos1=chaos, cfg0=cfg)
    try:
        events = _events(300, seed=5)
        _ingest_chunks(w0, events)
        w0.flush()
        w1.flush()
        assert w0.match_count + w1.match_count == _oracle(events)
        assert chaos.counters["dcn_kills"] > 0
        assert w0.guard.peer_counters[1]["reconnects"] > 0, (
            "a killed connection must evict the cached socket and redial")
    finally:
        _close_all(w0, w1)


def test_stale_socket_evicted_on_peer_restart(tmp_path):
    """Satellite: a cached socket to a restarted peer is broken; the next
    forward must evict + reconnect instead of failing forever."""
    store = LaneGroupSnapshotStore(str(tmp_path / "snaps"))
    cfg = DCNGuardConfig(retry_max=4, retry_base_s=0.02, retry_cap_s=0.1,
                         failure_threshold=10)
    w0, w1 = _mk_pair(cfg0=cfg, snapshot_store=store,
                      snapshot_every_frames=1)
    w1b = None
    try:
        events = _events(200, seed=9)
        half = len(events) // 2
        rows = [r for r, _ in events]
        tss = [t for _, t in events]
        w0.ingest(rows[:half], tss[:half])   # caches the data socket
        port1 = w1.port
        w1.close()
        w1b = DCNWorker(1, LaneTopology(8, 2), APP, "dev", port=port1,
                        peers={0: ("127.0.0.1", w0.port)}, epoch=1,
                        snapshot_store=store, restore=True,
                        snapshot_every_frames=1)
        w0.ingest(rows[half:], tss[half:])   # stale socket → evict → redial
        w0.flush()
        w1b.flush()
        assert w0.match_count + w1b.match_count == _oracle(events)
        assert w0.guard.peer_counters[1]["reconnects"] >= 1
    finally:
        _close_all(w0, w1)
        if w1b is not None:
            _close_all(w1b)


def test_forwarded_counts_only_acked_frames():
    """Satellite: a frame that was never acked (peer dead, spilled) must
    not advance ``forwarded``."""
    cfg = DCNGuardConfig(retry_max=1, retry_base_s=0.0,
                         failure_threshold=1)
    w0 = DCNWorker(0, LaneTopology(8, 2), APP, "dev", port=_free_port(),
                   peers={1: ("127.0.0.1", _free_port())},  # nobody there
                   guard_config=cfg)
    try:
        events = _events(120, seed=2)
        w0.ingest([r for r, _ in events], [t for _, t in events])
        assert w0.forwarded == 0, "unacked frames must not count forwarded"
        q = w0.guard.spill(1)
        assert q.spilled_frames > 0 and q.spilled_rows > 0
        assert w0.guard.peer_state(1) == PEER_DOWN
    finally:
        _close_all(w0)


def test_spill_and_inorder_replay_on_recovery(tmp_path):
    """Peer dies → frames spill (bounded, counted); peer returns → the
    heartbeat detects recovery and the backlog replays IN ORDER; totals
    match the oracle exactly."""
    store = LaneGroupSnapshotStore(str(tmp_path / "snaps"))
    cfg = DCNGuardConfig(retry_max=2, retry_base_s=0.005, retry_cap_s=0.02,
                         failure_threshold=2, down_cooldown_s=0.0,
                         probe_timeout_s=1.0,
                         spill_capacity_frames=512)
    w0, w1 = _mk_pair(cfg0=cfg, snapshot_store=store,
                      snapshot_every_frames=1)
    w1b = None
    try:
        events = _events(240, seed=13)
        third = len(events) // 3
        _ingest_chunks(w0, events[:third])           # phase A: healthy
        port1 = w1.port
        w1.close()
        _ingest_chunks(w0, events[third:2 * third])  # phase B: spills
        q = w0.guard.spill(1)
        assert q.spilled_frames > 0, "dead peer must spill, not lose"
        assert w0.guard.peer_state(1) == PEER_DOWN
        w0.guard.heartbeat_once()                    # probe fails: still down
        assert w0.guard.peer_state(1) == PEER_DOWN

        w1b = DCNWorker(1, LaneTopology(8, 2), APP, "dev", port=port1,
                        peers={0: ("127.0.0.1", w0.port)}, epoch=1,
                        snapshot_store=store, restore=True,
                        snapshot_every_frames=1)
        # an in-flight data-path retry may observe the recovery FIRST and
        # clear down_since before any probe runs — the heartbeat's backlog
        # sweep must drain the spill regardless
        w0.guard.on_send_ok(1)
        w0.guard.heartbeat_once()                    # sweep → replay
        assert w0.guard.peer_state(1) == PEER_HEALTHY
        assert q.empty, "recovery must drain the whole backlog in order"
        assert q.replayed_frames == q.spilled_frames >= 2
        _ingest_chunks(w0, events[2 * third:])       # phase C: healthy again
        w0.flush()
        w1b.flush()
        assert w0.match_count + w1b.match_count == _oracle(events), (
            "spill replay lost or duplicated rows")
    finally:
        _close_all(w0, w1)
        if w1b is not None:
            _close_all(w1b)


# -- failover: takeover + hand-back ------------------------------------------
def test_failover_takeover_and_rejoin(tmp_path):
    """Past the takeover deadline the survivor adopts the dead host's lane
    group from the latest snapshot revision, replays the spill locally, and
    serves both groups; when the host returns, the group hands back via
    K_ADOPT (the same handoff in reverse) and routing resumes."""
    clk = [0.0]
    store = LaneGroupSnapshotStore(str(tmp_path / "snaps"))
    cfg0 = DCNGuardConfig(retry_max=1, retry_base_s=0.0,
                          failure_threshold=1, down_cooldown_s=5.0,
                          probe_timeout_s=1.0, takeover_deadline_s=10.0,
                          spill_capacity_frames=512)
    p0, p1 = _free_port(), _free_port()
    w1 = DCNWorker(1, LaneTopology(8, 2), APP, "dev", port=p1,
                   peers={0: ("127.0.0.1", p0)},
                   snapshot_store=store, snapshot_every_frames=1)
    w0 = DCNWorker(0, LaneTopology(8, 2), APP, "dev", port=p0,
                   peers={1: ("127.0.0.1", p1)}, guard_config=cfg0,
                   snapshot_store=store, clock=lambda: clk[0])
    w1b = None
    try:
        events = _events(320, seed=17)
        quarter = len(events) // 4

        _ingest_chunks(w0, events[:quarter])              # A: healthy
        w1.close()                                        # host 1 dies
        _ingest_chunks(w0, events[quarter:2 * quarter])   # B: spills
        assert w0.guard.peer_state(1) == PEER_DOWN
        clk[0] = 11.0                                     # past the deadline
        w0.guard.heartbeat_once()
        assert w0.takeovers == 1
        assert sorted(w0.topo.groups_owned_by(0)) == [0, 1]
        assert w0.guard.spill(1).empty, "takeover must replay the spill"
        _ingest_chunks(w0, events[2 * quarter:3 * quarter])   # C: all local
        w0.flush()
        assert w0.match_count == _oracle(events[:3 * quarter]), (
            "adopted lane group lost rows (snapshot restore or local "
            "replay broke)")

        # host 1 returns as a standby (owns nothing until the handoff)
        w1b = DCNWorker(1, LaneTopology(8, 2, owner={0: 0, 1: 0}), APP,
                        "dev", port=p1, peers={0: ("127.0.0.1", p0)},
                        epoch=1, snapshot_store=store,
                        snapshot_every_frames=1)
        clk[0] = 30.0
        w0.guard.heartbeat_once()                         # recovery → release
        assert w0.rejoins == 1
        assert w0.topo.owner[1] == 1 and w1b.takeovers == 1
        assert sorted(w1b.topo.groups_owned_by(1)) == [1]

        _ingest_chunks(w0, events[3 * quarter:])          # D: routed again
        w0.flush()
        w1b.flush()
        assert w0.match_count + w1b.match_count == _oracle(events), (
            "hand-back lost or duplicated rows")
        assert w0.forwarded > 0 and w1b.received > 0
    finally:
        _close_all(w0, w1)
        if w1b is not None:
            _close_all(w1b)


# -- the kill-peer soak: two real OS processes -------------------------------
def _soak_child_main(pipe, port, parent_port, store_dir, epoch, restore):
    # CPU like the parent: JAX_PLATFORMS=cpu is inherited (tests/conftest.py)
    from siddhi_tpu.resilience.dcn_guard import LaneGroupSnapshotStore
    from siddhi_tpu.tpu.dcn import DCNWorker, LaneTopology
    w = DCNWorker(1, LaneTopology(8, 2), APP, "dev", port=port,
                  peers={0: ("127.0.0.1", parent_port)}, epoch=epoch,
                  snapshot_store=LaneGroupSnapshotStore(store_dir),
                  restore=restore, snapshot_every_frames=1)
    pipe.send(w.port)
    w._stop.wait(timeout=300)


@pytest.mark.chaos
def test_kill_peer_soak_exactly_once(tmp_path):
    """THE acceptance soak: peer process SIGKILLed mid-ingest, frames spill,
    the process restarts (snapshot restore + epoch bump), the backlog
    replays — total matches equal the single-host oracle, zero loss, zero
    duplicates."""
    store_dir = str(tmp_path / "snaps")
    os.makedirs(store_dir, exist_ok=True)
    ctx = mp.get_context("spawn")
    env_backup = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    p0, p1 = _free_port(), _free_port()
    parent_conn, child_conn = ctx.Pipe()
    proc = ctx.Process(target=_soak_child_main,
                       args=(child_conn, p1, p0, store_dir, 0, False),
                       daemon=True)
    proc.start()
    w0 = None
    proc2 = None
    try:
        assert parent_conn.poll(120), "child worker never came up"
        parent_conn.recv()
        cfg = DCNGuardConfig(retry_max=2, retry_base_s=0.01,
                             retry_cap_s=0.05, failure_threshold=2,
                             down_cooldown_s=0.05, probe_timeout_s=2.0,
                             spill_capacity_frames=1024)
        w0 = DCNWorker(0, LaneTopology(8, 2), APP, "dev", port=p0,
                       peers={1: ("127.0.0.1", p1)}, guard_config=cfg,
                       io_timeout_s=5.0, connect_timeout_s=2.0)
        events = _events(400, seed=29)
        chunks = [events[i:i + 40] for i in range(0, len(events), 40)]

        for i, chunk in enumerate(chunks):
            if i == 4:
                proc.kill()                       # SIGKILL mid-ingest
                proc.join(timeout=30)
            w0.ingest([r for r, _ in chunk], [t for _, t in chunk])

        q = w0.guard.spill(1)
        assert q.spilled_frames > 0, "the kill never produced a spill"

        parent_conn2, child_conn2 = ctx.Pipe()
        proc2 = ctx.Process(target=_soak_child_main,
                            args=(child_conn2, p1, p0, store_dir, 1, True),
                            daemon=True)
        proc2.start()
        assert parent_conn2.poll(120), "restarted worker never came up"
        parent_conn2.recv()

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            w0.guard.heartbeat_once()
            if w0.guard.peer_state(1) == PEER_HEALTHY and q.empty:
                break
            time.sleep(0.1)
        assert q.empty, "spill backlog never drained after restart"

        w0.flush()
        s = socket.create_connection(("127.0.0.1", p1), timeout=10)
        send_msg(s, K_FLUSH)
        reply = recv_msg(s, timeout=60)
        assert reply and reply[0] == K_FLUSHED
        import struct
        peer_matches = struct.unpack(">q", reply[1])[0]
        s.close()

        total = w0.match_count + peer_matches
        oracle = _oracle(events)
        assert total == oracle, (
            f"kill-restart soak: {total} != oracle {oracle} "
            f"(h0={w0.match_count}, h1={peer_matches}, "
            f"spilled={q.spilled_frames}, replayed={q.replayed_frames})")
    finally:
        if env_backup is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = env_backup
        if w0 is not None:
            _close_all(w0)
        proc.terminate()
        proc.join(timeout=10)
        if proc2 is not None:
            proc2.terminate()
            proc2.join(timeout=10)


# -- shutdown / serve-thread hygiene -----------------------------------------
def test_serve_threads_exit_on_close():
    """Satellite: server-side connection threads must exit on close()
    instead of blocking in recv forever."""
    w = DCNWorker(0, LaneTopology(8, 2), APP, "dev", port=_free_port(),
                  peers={}, io_timeout_s=0.3)
    s = socket.create_connection(("127.0.0.1", w.port), timeout=5)
    send_msg(s, K_FLUSH)
    assert recv_msg(s, timeout=10)[0] == K_FLUSHED   # thread is serving
    w.close()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and (
            w._accept_thread.is_alive()
            or any(t.is_alive() for t in w._serve_threads)):
        time.sleep(0.05)
    assert not w._accept_thread.is_alive(), "accept loop did not exit"
    assert not any(t.is_alive() for t in w._serve_threads), (
        "a serve thread is still blocked after close()")
    s.close()


def test_recv_without_deadline_rejected():
    """No DCN call path may block without a deadline — a socket handed to
    the framing layer with no timeout is an error, not a hang."""
    a, b = socket.socketpair()
    try:
        a.settimeout(None)
        with pytest.raises(ValueError):
            recv_msg(a, timeout=None)
    finally:
        a.close()
        b.close()


# -- service endpoint + metrics ----------------------------------------------
def test_dcn_service_endpoint_and_metrics():
    from urllib.request import urlopen

    from siddhi_tpu.service import SiddhiService

    svc = SiddhiService(port=0)
    svc.start()
    w = None
    try:
        code, payload = svc.deploy(
            "@app(name='DcnApp') define stream S (dev string, v double); "
            "from S select dev insert into O;")
        assert code == 200
        base = f"http://127.0.0.1:{svc.port}/siddhi-apps/DcnApp"
        import json
        with urlopen(base + "/dcn", timeout=10) as r:
            body = json.loads(r.read())
        assert body == {"status": "OK", "enabled": False}

        w = DCNWorker(0, LaneTopology(8, 2), APP, "dev", port=_free_port(),
                      peers={1: ("127.0.0.1", _free_port())})
        rt = svc.runtimes["DcnApp"]
        rt.dcn_worker = w
        w.register_metrics(rt.ctx.statistics_manager)
        with urlopen(base + "/dcn", timeout=10) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert body["owned_groups"] == [0]
        assert body["peers"] == {} or "1" not in body["peers"] or \
            "state" in body["peers"]["1"]
        assert body["topology"]["owner"] == {"0": 0, "1": 1}

        with urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert 'siddhi_tpu_dcn_peer_state{app="DcnApp",peer="1"}' in text
        assert "siddhi_tpu_dcn_takeovers_total" in text
        assert "siddhi_tpu_dcn_spill_depth" in text

        # closing the worker unregisters its trackers (no dead gauges)
        w.close()
        w = None
        with urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "siddhi_tpu_dcn_" not in text
    finally:
        if w is not None:
            _close_all(w)
        svc.stop()


# -- lint: every DCN call path carries a deadline ----------------------------
def test_check_socket_timeouts_lint_passes():
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_socket_timeouts.py")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_check_socket_timeouts_lint_catches_offenders(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "check_socket_timeouts",
        os.path.join(REPO, "scripts", "check_socket_timeouts.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    offender = tmp_path / "offender.py"
    offender.write_text(
        "import socket\n"
        "def dial(addr):\n"
        "    return socket.create_connection(addr)\n"
        "def drain(sock):\n"
        "    return sock.recv(4096)\n"
        "def ok(sock):\n"
        "    sock.settimeout(5.0)\n"
        "    return sock.recv(4096)\n"
        "def serve(listener):\n"
        "    return listener.accept()\n"
        "def serve_ok(listener):\n"
        "    listener.settimeout(0.5)\n"
        "    return listener.accept()\n")
    problems = mod.check_file(str(offender))
    assert len(problems) == 3, problems
    assert any("create_connection" in p for p in problems)
    assert any("blocking recv in 'drain'" in p for p in problems)
    # ISSUE 16: undeadlined accept loops (the procmesh serve loops) are
    # findings too — they'd never observe their stop flag
    assert any("blocking accept in 'serve'" in p for p in problems)
