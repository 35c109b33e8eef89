"""Multi-host DCN prototype: 2 processes, cross-host ingest routing,
per-shard egress (SURVEY §2.3 last row; VERDICT r3 item 10).

Process 0 (this test) and process 1 (spawned) each own half of an 8-lane
global lane space. Every event is offered to process 0; rows owned by
process 1's lanes travel over a real socket in bulk frames. Combined match
counts must equal the single-engine host oracle.
"""

import multiprocessing as mp
import os
import sys

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.tpu.dcn import (
    DCNWorker,
    K_FLUSH,
    K_FLUSHED,
    LaneTopology,
    pack_rows,
    recv_msg,
    send_msg,
    unpack_rows,
)

APP = """
define stream S (dev string, v double);
partition with (dev of S)
begin
from every e1=S[v > 50.0] -> e2=S[v > e1.v]
select e1.v as v1, e2.v as v2 insert into Alerts;
end;
"""


def _events(n=600, keys=12, seed=21):
    import random
    rng = random.Random(seed)
    out = []
    for i in range(n):
        out.append(([f"dev{rng.randrange(keys)}",
                     round(rng.uniform(0.0, 100.0), 2)], 1000 + i))
    return out


def _child_main(conn_port_pipe):
    """Worker process 1: owns lanes [4, 8); serves DCN ingest."""
    # CPU like the parent: JAX_PLATFORMS=cpu is inherited (tests/conftest.py)
    topo = LaneTopology(8, 2)
    w = DCNWorker(1, topo, APP, "dev", port=0, peers={})
    conn_port_pipe.send(w.port)
    w._stop.wait(timeout=120)


def test_soa_wire_format_roundtrip_and_size():
    """The binary SoA frame (native/ingress.cpp's lane-buffer layout on the
    wire) must round-trip exactly — including nulls and every column type —
    and beat the r4 JSON framing on bytes per row (the bandwidth note:
    numeric columns ship as dense typed arrays, not digit strings)."""
    import json
    import random

    rng = random.Random(9)
    types = "sidlb"
    rows = []
    for i in range(500):
        rows.append([
            None if i % 97 == 0 else f"dev{rng.randrange(1000)}",
            None if i % 89 == 0 else rng.randrange(-2**31, 2**31),
            rng.uniform(-1e6, 1e6),
            rng.randrange(-2**62, 2**62),
            rng.random() < 0.5,
        ])
    tss = [1_000_000 + i for i in range(len(rows))]

    payload = pack_rows(types, rows, tss)
    back_rows, back_tss = unpack_rows(payload)
    assert back_tss == tss
    for r, b in zip(rows, back_rows):
        assert r[0] == b[0] and r[1] == b[1] and r[3] == b[3] and r[4] == b[4]
        assert b[2] == r[2] or abs(b[2] - r[2]) < 1e-9 * max(1, abs(r[2]))

    json_payload = json.dumps([[r, t] for r, t in zip(rows, tss)]).encode()
    assert len(payload) < len(json_payload), (
        f"SoA {len(payload)}B should undercut JSON {len(json_payload)}B")


def test_soa_wire_format_empty_and_float_width():
    rows, tss = unpack_rows(pack_rows("df", [], []))
    assert rows == [] and tss == []
    # f = f32 on the wire: value survives an f32 round-trip
    rows, _ = unpack_rows(pack_rows("f", [[1.5], [None]], [1, 2]))
    assert rows == [[1.5], [None]]


def test_two_process_dcn_ingest_routing():
    ctx = mp.get_context("spawn")
    parent_conn, child_conn = ctx.Pipe()
    env_backup = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    proc = ctx.Process(target=_child_main, args=(child_conn,), daemon=True)
    proc.start()
    try:
        child_port = parent_conn.recv()

        topo = LaneTopology(8, 2)
        w0 = DCNWorker(0, topo, APP, "dev", port=0,
                       peers={1: ("127.0.0.1", child_port)})
        events = _events()
        rows = [r for r, _ in events]
        tss = [t for _, t in events]
        # everything enters at host 0; peer-owned rows cross the socket
        w0.ingest(rows, tss)
        w0.flush()
        assert w0.forwarded > 0, "no cross-host traffic — topology degenerate"

        # flush barrier to the peer; per-shard egress: each host reports its
        # own lanes' matches
        import socket
        import struct
        s = socket.create_connection(("127.0.0.1", child_port), timeout=10)
        send_msg(s, K_FLUSH)
        reply = recv_msg(s)
        assert reply and reply[0] == K_FLUSHED
        peer_matches = struct.unpack(">q", reply[1])[0]
        s.close()

        total = w0.match_count + peer_matches

        # single-engine oracle over the identical stream
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(APP, playback=True)
        host = []
        rt.add_callback("Alerts", StreamCallback(
            lambda evs: host.extend(evs)))
        rt.start()
        ih = rt.input_handler("S")
        for row, ts in events:
            ih.send(list(row), timestamp=ts)
        m.shutdown()

        assert total == len(host), (
            f"sharded total {total} (h0={w0.match_count}, h1={peer_matches})"
            f" != oracle {len(host)}; forwarded={w0.forwarded}")
        assert peer_matches > 0 and w0.match_count > 0, (
            "both shards should produce matches on this keyset")
        w0.close()
    finally:
        if env_backup is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = env_backup
        proc.terminate()
        proc.join(timeout=10)
