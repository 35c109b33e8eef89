"""Native C++ ingress: parity with the Python packers and lane router.

Reference analog: StreamJunction ring ingress + event converters
(stream/StreamJunction.java:254-316, event/stream/converter/)."""

import numpy as np
import pytest

from siddhi_tpu.native import NativeIngress, native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native toolchain unavailable")


def test_csv_basic_types():
    ing = NativeIngress("sdlib", key_col=-1, n_lanes=1, capacity=16)
    data = b"dev1,3.5,42,7,true\ndev2,-1.25,-9,0,false\n"
    consumed = ing.ingest_csv(data, base_ts=100)
    assert consumed == len(data)
    assert ing.lane_len(0) == 2
    b = ing.emit_lane(0)
    assert b["count"] == 2
    assert ing.decode(int(b["cols"][0][0])) == "dev1"
    assert ing.decode(int(b["cols"][0][1])) == "dev2"
    assert b["cols"][1][0] == 3.5 and b["cols"][1][1] == -1.25
    assert b["cols"][2][0] == 42 and b["cols"][2][1] == -9
    assert b["cols"][3][0] == 7 and b["cols"][3][1] == 0
    assert b["cols"][4][0] == 1 and b["cols"][4][1] == 0
    assert list(b["ts"][:2]) == [100, 101]
    assert b["valid"][:2].all() and not b["valid"][2:].any()


def test_ts_last_column():
    ing = NativeIngress("sd", key_col=-1, n_lanes=1, capacity=8)
    ing.ingest_csv(b"a,1.0,5000\nb,2.0,6000\n", ts_last=True)
    b = ing.emit_lane(0)
    assert list(b["ts"][:2]) == [5000, 6000]


def test_lane_routing_matches_python_crc32():
    from siddhi_tpu.tpu.partition import _hash_key

    ing = NativeIngress("sd", key_col=0, n_lanes=64, capacity=128)
    keys = [f"dev{i}" for i in range(500)] + ["", "unicode-éé"]
    for k in keys:
        assert ing.lane_of(k) == _hash_key(k) % 64, k


def test_lane_routing_on_ingest():
    from siddhi_tpu.tpu.partition import _hash_key

    ing = NativeIngress("sd", key_col=0, n_lanes=4, capacity=64)
    rows = [(f"dev{i}", float(i)) for i in range(40)]
    data = "".join(f"{k},{v}\n" for k, v in rows).encode()
    assert ing.ingest_csv(data) == len(data)
    per_lane = {ln: ing.lane_len(ln) for ln in range(4)}
    expect = {ln: 0 for ln in range(4)}
    for k, _ in rows:
        expect[_hash_key(k) % 4] += 1
    assert per_lane == expect
    # values landed with their keys
    b = ing.emit_lane(0)
    for i in range(b["count"]):
        k = ing.decode(int(b["cols"][0][i]))
        assert _hash_key(k) % 4 == 0
        assert b["cols"][1][i] == float(k[3:])


def test_backpressure_partial_consume():
    ing = NativeIngress("sd", key_col=-1, n_lanes=1, capacity=3)
    data = b"a,1\nb,2\nc,3\nd,4\ne,5\n"
    consumed = ing.ingest_csv(data)
    assert consumed == len(b"a,1\nb,2\nc,3\n")
    assert ing.lane_len(0) == 3
    ing.emit_lane(0)
    rest = data[consumed:]
    assert ing.ingest_csv(rest) == len(rest)
    b = ing.emit_lane(0)
    assert b["count"] == 2
    assert ing.decode(int(b["cols"][0][0])) == "d"


def test_malformed_lines_counted_not_fatal():
    ing = NativeIngress("sd", key_col=-1, n_lanes=1, capacity=8)
    data = b"a,1.5\nbad_line\nb,not_a_number\nc,2.5\n"
    assert ing.ingest_csv(data) == len(data)
    assert ing.parse_errors == 2
    b = ing.emit_lane(0)
    assert b["count"] == 2
    assert ing.decode(int(b["cols"][0][1])) == "c"


def test_partial_tail_framing():
    ing = NativeIngress("sd", key_col=-1, n_lanes=1, capacity=8)
    consumed = ing.ingest_csv(b"a,1\nb,2", final=False)
    assert consumed == len(b"a,1\n")
    assert ing.lane_len(0) == 1
    # resume with the rest
    assert ing.ingest_csv(b"b,2\n", final=True) == 4
    assert ing.lane_len(0) == 2


def test_dict_shared_and_stable():
    ing = NativeIngress("ss", key_col=-1, n_lanes=1, capacity=8)
    c1 = ing.encode("hello")
    c2 = ing.encode("world")
    assert ing.encode("hello") == c1
    assert ing.decode(c1) == "hello" and ing.decode(c2) == "world"
    assert ing.decode(0) is None
    # codes from CSV path agree with encode()
    ing.ingest_csv(b"hello,world\n")
    b = ing.emit_lane(0)
    assert int(b["cols"][0][0]) == c1 and int(b["cols"][1][0]) == c2


def test_empty_fields_become_none_zero():
    ing = NativeIngress("sd", key_col=-1, n_lanes=1, capacity=8)
    ing.ingest_csv(b",\n")
    b = ing.emit_lane(0)
    assert b["count"] == 1
    assert int(b["cols"][0][0]) == 0 and b["cols"][1][0] == 0.0


def test_throughput_smoke():
    # not a benchmark — just ensures the bulk path takes 100k rows whole
    ing = NativeIngress("sd", key_col=0, n_lanes=16, capacity=100_000)
    rows = "".join(f"dev{i % 50},{i * 0.5}\n" for i in range(100_000)).encode()
    assert ing.ingest_csv(rows) == len(rows)
    assert sum(ing.lane_len(i) for i in range(16)) == 100_000


def test_partitioned_nfa_native_csv_parity():
    """End-to-end: C++ CSV ingress → partitioned device NFA matches the
    Python send() path exactly (same matches, same decoded rows)."""
    from siddhi_tpu.tpu.partition import PartitionedNFARuntime

    app = """
define stream S (dev string, v double);
from every e1=S[v > 50.0] -> e2=S[v > e1.v] within 4000
select e1.dev as dev, e1.v as v1, e2.v as v2 insert into Alerts;
"""
    import random
    rng = random.Random(7)
    events = [(f"dev{rng.randrange(20)}", round(rng.uniform(0, 100), 3),
               1000 + i) for i in range(3000)]

    kw = dict(num_partitions=8, key_attr="dev", slot_capacity=32,
              lane_batch=64, mesh=None)
    rt_py = PartitionedNFARuntime(app, **kw)
    for dev, v, ts in events:
        rt_py.send("S", [dev, v], ts)
    rt_py.flush(decode=True)
    py_matches = rt_py.match_count

    rt_c = PartitionedNFARuntime(app, **kw)
    rt_c.enable_native_ingress()
    csv = "".join(f"{dev},{v},{ts}\n" for dev, v, ts in events).encode()
    rows_c = rt_c.ingest_csv(csv, ts_last=True, decode=True)
    rows_c += rt_c.flush_native(decode=True) or []
    assert rt_c.match_count == py_matches
    assert rt_c.drop_count == rt_py.drop_count
    assert len(rows_c) == rt_c.match_count
    for r in rows_c:
        assert r[0].startswith("dev") and r[2] > r[1] > 50.0


def test_mixed_send_and_native_ingest_rejected():
    from siddhi_tpu.tpu.partition import PartitionedNFARuntime

    rt = PartitionedNFARuntime("""
define stream S (dev string, v double);
from every e1=S[v > 50.0] -> e2=S[v > e1.v]
select e1.v as a, e2.v as b insert into Alerts;
""", num_partitions=2, key_attr="dev", slot_capacity=8, lane_batch=16)
    rt.enable_native_ingress()
    with pytest.raises(RuntimeError, match="native ingress"):
        rt.send("S", ["d1", 60.0], 1000)


def test_so_not_built_from_this_source_is_rebuilt(tmp_path, monkeypatch):
    """Freshness is a hash of ingress.cpp, not an mtime: a binary built from
    another source — newer on disk, as after a copy or checkout — is never
    loaded; and a source that does not compile leaves the compiler's words
    behind instead of a silent False."""
    import ctypes
    import os
    import shutil

    from siddhi_tpu import native

    built_from_old = native.so_path()
    assert os.path.exists(built_from_old)       # pytestmark loaded it
    src = tmp_path / "ingress.cpp"
    src.write_bytes(open(native._SRC, "rb").read()
                    + b'\nextern "C" int sp_marker() { return 4242; }\n')
    build = tmp_path / "_build"
    build.mkdir()
    stale = build / os.path.basename(built_from_old)
    legacy = build / "libsiddhi_ingress.so"
    shutil.copy(built_from_old, stale)
    shutil.copy(built_from_old, legacy)
    later = os.path.getmtime(src) + 3600
    os.utime(stale, (later, later))
    os.utime(legacy, (later, later))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_unavailable_reason", None)

    fresh = native.so_path()
    assert fresh not in (str(stale), str(legacy))
    assert not os.path.exists(fresh)
    assert native.native_available()
    assert os.path.exists(fresh)
    assert ctypes.CDLL(fresh).sp_marker() == 4242
    assert native._lib.sp_marker() == 4242

    src.write_bytes(b"this is not C++\n")
    monkeypatch.setattr(native, "_lib", None)
    assert not native.native_available()
    assert "g++ exited" in native.native_unavailable_reason()
    with pytest.raises(RuntimeError, match="g\\+\\+ exited"):
        NativeIngress("sd")
