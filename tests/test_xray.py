"""X-Ray (ISSUE 10): detection-latency attribution, cross-host trace
stitching, and the engine flight recorder.

- waterfall spans (start offsets + phase classification) and trace
  endpoint ergonomics (?limit= / ?stream=);
- per-query per-phase histograms whose means reconcile against the
  end-to-end mean, served at GET /siddhi-apps/{name}/latency;
- OpenMetrics exemplars: tail buckets link to concrete traces, and the
  exposition without traces armed is byte-identical to before;
- cross-host stitching: sampled TraceContexts ride K_ROWS frames through
  retry/dedup, spill replay and lane-group takeover (two loopback
  workers, one trace id spanning both hosts with a dcn hop span);
- flight recorder: bounded ring, transition dedupe, fault dump, HTTP
  endpoint;
- the ≤5% overhead pin (tracing at default sampling + recorder armed vs
  disarmed on the columnar micro-corpus);
- scripts/check_span_coverage.py gating from tier-1.
"""

import http.client
import json
import os
import random
import subprocess
import sys
import time

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.observability import FlightRecorder, PipelineTracer
from siddhi_tpu.observability.phases import PHASES, phase_of_stage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


# ---------------------------------------------------------------------------
# flight recorder unit behavior
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_bounded_and_ordered():
    fr = FlightRecorder(capacity=16, app_name="a")
    for i in range(100):
        fr.record("flow", f"k{i}", site="s")
    assert len(fr.ring) == 16
    entries = fr.export()
    assert len(entries) == 16
    # timestamp + seq strictly ordered, oldest evicted
    seqs = [e["seq"] for e in entries]
    assert seqs == sorted(seqs) and seqs[0] == 84
    ts = [e["t"] for e in entries]
    assert ts == sorted(ts)
    assert fr.export(limit=4) == entries[-4:]
    assert fr.export(category="breaker") == []


def test_flight_recorder_transition_dedupe():
    fr = FlightRecorder(capacity=64)
    assert fr.record_transition("flow", "flush:capacity", site="q")
    for _ in range(50):
        assert not fr.record_transition("flow", "flush:capacity", site="q")
    assert fr.record_transition("flow", "flush:deadline", site="q")
    # a DIFFERENT site has its own transition state
    assert fr.record_transition("flow", "flush:capacity", site="q2")
    kinds = [e["kind"] for e in fr.export()]
    assert kinds == ["flush:capacity", "flush:deadline", "flush:capacity"]


def test_flight_recorder_fault_dump(tmp_path):
    fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path), app_name="app1")
    fr.record("device", "step_failed", site="q", trace_id=7)
    path = fr.on_fault("device_quarantine", site="q")
    assert path is not None and os.path.exists(path)
    dumped = json.load(open(path))
    assert dumped["reason"] == "device_quarantine"
    assert dumped["entries"][0]["kind"] == "step_failed"
    assert dumped["entries"][0]["trace_id"] == 7
    # no dump dir → no-op, never raises
    assert FlightRecorder(capacity=8).on_fault("x") is None


# ---------------------------------------------------------------------------
# waterfall spans + trace endpoint ergonomics
# ---------------------------------------------------------------------------

TRACED_TWO_STREAMS = """
@app(name='Waterfall')
@app:trace(sample='1/1', ring='64')
define stream S (v double);
define stream T (v double);
@sink(type='inMemory', topic='xw_t', @map(type='passThrough'))
define stream O (v double);
from S[v > 0.0] select v insert into O;
from T[v > 0.0] select v insert into O;
"""


def test_span_waterfall_offsets_and_phase_classification(manager):
    rt = manager.create_siddhi_app_runtime(TRACED_TWO_STREAMS,
                                           playback=True)
    rt.start()
    for i in range(6):
        rt.input_handler("S").send([1.0 + i], timestamp=1000 + i)
    rt.input_handler("T").send([5.0], timestamp=2000)
    tracer = rt.observability.tracer
    traces = tracer.export()
    assert len(traces) == 7
    for t in traces:
        offs = [s["start_offset_ms"] for s in t["spans"]]
        assert all(o >= 0.0 for o in offs)
        for s in t["spans"]:
            assert s["phase"] in PHASES
        # the ingress span covers the whole synchronous journey: nested
        # spans (query, sink) start at or after it
        ing = [s for s in t["spans"] if s["stage"] == "ingress"]
        assert ing and ing[0]["start_offset_ms"] <= min(offs) + 1e-6
    # endpoint ergonomics: ?stream= and ?limit= compose
    assert len(tracer.export(stream="T")) == 1
    assert len(tracer.export(stream="S")) == 6
    assert len(tracer.export(limit=3, stream="S")) == 3
    assert tracer.export(limit=0) == []


def test_trace_http_endpoint_stream_filter():
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(playback=True)
    svc.start()
    try:
        code, _ = svc.deploy(TRACED_TWO_STREAMS)
        assert code == 200
        rt = svc.runtimes["Waterfall"]
        for i in range(4):
            rt.input_handler("S").send([1.0 + i], timestamp=1000 + i)
        rt.input_handler("T").send([5.0], timestamp=2000)

        conn = http.client.HTTPConnection("127.0.0.1", svc.port, timeout=10)
        conn.request("GET", "/siddhi-apps/Waterfall/trace?stream=T")
        body = json.loads(conn.getresponse().read().decode())
        assert [t["stream"] for t in body["traces"]] == ["T"]
        conn.request("GET",
                     "/siddhi-apps/Waterfall/trace?stream=S&limit=2")
        body = json.loads(conn.getresponse().read().decode())
        assert len(body["traces"]) == 2
        assert all(t["stream"] == "S" for t in body["traces"])
        conn.close()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# phase attribution: reconciliation against end-to-end
# ---------------------------------------------------------------------------

DEVICE_APP = """
@app(name='Attr')
@app:adaptive(target.ms='25', min='16', initial='32')
define stream S (v double);
@sink(type='inMemory', topic='xattr_t', @map(type='passThrough'))
define stream O (t double);
@info(name='agg')
@device(batch='64') from S#window.length(16) select sum(v) as t insert into O;
"""


def test_latency_report_phases_reconcile_with_end_to_end(manager):
    rt = manager.create_siddhi_app_runtime(DEVICE_APP, playback=True)
    rt.start()
    ih = rt.input_handler("S")
    for i in range(400):
        ih.send([float(i)], timestamp=1000 + i)
    rt.flush_device()
    report = rt.observability.latency_report()
    q = report["queries"]["agg"]
    e2e = q["end_to_end"]
    assert e2e["count"] >= 400          # event-weighted
    phases = q["phases"]
    assert "fill_wait" in phases and "device_step" in phases
    # the acceptance bar: sum of phase means within 10% of the e2e mean
    assert q["end_to_end_mean_ms"] > 0.0
    assert abs(q["phase_mean_sum_ms"] - q["end_to_end_mean_ms"]) \
        <= 0.10 * q["end_to_end_mean_ms"]
    assert 0.9 <= q["reconciliation_ratio"] <= 1.1
    # the deadline-flush queueing share is its own field (0.0 here: every
    # flush was capacity/adaptive/drain, none deadline)
    assert "deadline_flush_queueing_share" in q
    assert 0.0 <= q["deadline_flush_queueing_share"] <= 1.0
    # phase histograms render as ONE family with a bounded phase label
    from siddhi_tpu.observability import render
    text = render([rt.ctx.statistics_manager])
    assert 'siddhi_tpu_phase_latency_seconds_bucket' in text
    assert 'phase="fill_wait"' in text and 'phase="device_step"' in text


def test_latency_http_endpoint(manager):
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(manager, port=0)
    rt = manager.create_siddhi_app_runtime(DEVICE_APP, playback=True)
    rt.start()
    svc.runtimes = {rt.name: rt}
    try:
        ih = rt.input_handler("S")
        for i in range(100):
            ih.send([float(i)], timestamp=1000 + i)
        rt.flush_device()
        code, payload = svc.latency_stats("Attr")
        assert code == 200 and "agg" in payload["queries"]
        code, _ = svc.latency_stats("Ghost")
        assert code == 404
    finally:
        svc._server.server_close()


def test_interpreter_queries_report_host_exec_phase(manager):
    rt = manager.create_siddhi_app_runtime(
        "@app(name='Hq', statistics='true')\n"
        "define stream S (v double);\n"
        "@info(name='f') from S[v > 1.0] select v insert into O;",
        playback=True)
    rt.start()
    for i in range(20):
        rt.input_handler("S").send([float(i)], timestamp=1000 + i)
    report = rt.observability.latency_report()
    q = report["queries"]["f"]
    assert q["end_to_end"]["count"] == 20
    assert q["phases"]["host_exec"]["count"] == 20


# ---------------------------------------------------------------------------
# exemplars: only when sampled; byte-identical without traces
# ---------------------------------------------------------------------------

def _stats_app(name, traced):
    return (f"@app(name='{name}', statistics='true')\n"
            + ("@app:trace(sample='1/1')\n" if traced else "")
            + "define stream S (v double);\n"
            "@info(name='f') from S[v > 0.0] select v insert into O;")


def test_exemplars_only_when_sampled_and_negotiated(manager):
    from siddhi_tpu.observability import render
    rt_plain = manager.create_siddhi_app_runtime(_stats_app("P", False),
                                                 playback=True)
    rt_traced = manager.create_siddhi_app_runtime(_stats_app("T", True),
                                                  playback=True)
    rt_plain.start()
    rt_traced.start()
    for i in range(10):
        rt_plain.input_handler("S").send([1.0 + i], timestamp=1000 + i)
        rt_traced.input_handler("S").send([1.0 + i], timestamp=1000 + i)
    # the default (Prometheus 0.0.4) exposition NEVER carries exemplars —
    # strict parsers reject them — so it stays byte-identical to pre-X-Ray
    # whether or not tracing armed
    for sm in (rt_plain.ctx.statistics_manager,
               rt_traced.ctx.statistics_manager):
        plain = render([sm])
        assert " # {" not in plain, "exemplar leaked into 0.0.4 exposition"
        assert render([sm]) == plain        # deterministic re-render
    # untraced app: even the OpenMetrics render has none to show
    assert " # {" not in render([rt_plain.ctx.statistics_manager],
                                with_exemplars=True)
    traced = render([rt_traced.ctx.statistics_manager],
                    with_exemplars=True)
    ex_lines = [ln for ln in traced.splitlines() if " # {" in ln]
    assert ex_lines, "traced app produced no exemplars"
    for ln in ex_lines:
        assert "_bucket{" in ln and 'trace_id="' in ln
    # the lint validates exemplar syntax + cardinality on this output
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cmn", os.path.join(REPO, "scripts", "check_metric_names.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.check(traced) == []


def test_metrics_endpoint_openmetrics_negotiation(manager):
    """Exemplars ride only an Accept-negotiated OpenMetrics scrape; the
    default scrape stays strict 0.0.4 with no exemplar syntax."""
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(manager, port=0)
    rt = manager.create_siddhi_app_runtime(_stats_app("Nego", True),
                                           playback=True)
    rt.start()
    svc.runtimes = {rt.name: rt}
    svc.start()
    try:
        for i in range(10):
            rt.input_handler("S").send([1.0 + i], timestamp=1000 + i)
        conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                          timeout=10)
        conn.request("GET", "/siddhi-apps/Nego/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert "version=0.0.4" in resp.getheader("Content-Type")
        assert " # {" not in body and "# EOF" not in body
        conn.request("GET", "/siddhi-apps/Nego/metrics", headers={
            "Accept": "application/openmetrics-text; version=1.0.0"})
        resp = conn.getresponse()
        body = resp.read().decode()
        assert "openmetrics-text" in resp.getheader("Content-Type")
        assert " # {" in body and body.endswith("# EOF\n")
        conn.close()
    finally:
        svc.stop()


def test_metric_lint_catches_exemplar_and_cardinality_offenders():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cmn2", os.path.join(REPO, "scripts", "check_metric_names.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    bad = "\n".join(
        ["# TYPE siddhi_tpu_h histogram",
         # exemplar on a gauge-ish _count line: misplaced
         'siddhi_tpu_h_count{app="a"} 1 # {trace_id="1"} 0.5 1.0',
         # exemplar value exceeding its bucket bound
         'siddhi_tpu_h_bucket{app="a",le="0.1"} 1 # {trace_id="2"} 0.5 1.0',
         # foreign exemplar label
         'siddhi_tpu_h_bucket{app="a",le="0.2"} 1 # {user_id="u"} 0.1 1.0',
         'siddhi_tpu_h_bucket{app="a",le="+Inf"} 3 # {trace_id="3"} 0.3',
         'siddhi_tpu_h_sum{app="a"} 0.9',
         # unbounded identity label
         "# TYPE siddhi_tpu_g gauge",
         'siddhi_tpu_g{app="a",tenant_id="t1"} 1'])
    problems = lint.check(bad)
    assert any("non-bucket" in p for p in problems)
    assert any("exceeds its bucket" in p for p in problems)
    assert any("may ride an exemplar" in p for p in problems)
    assert any("unbounded identity" in p for p in problems)
    # cardinality bound: one family fanning a label past the cap
    wide = ["# TYPE siddhi_tpu_w gauge"] + [
        f'siddhi_tpu_w{{app="a",shard="s{i}"}} 1'
        for i in range(lint.MAX_LABEL_VALUES + 1)]
    problems = lint.check("\n".join(wide))
    assert any("cardinality" in p for p in problems)


# ---------------------------------------------------------------------------
# cross-host stitching (two loopback workers)
# ---------------------------------------------------------------------------

DCN_APP = """
define stream S (dev string, v double);
partition with (dev of S)
begin
from every e1=S[v > 50.0] -> e2=S[v > e1.v]
select e1.v as v1, e2.v as v2 insert into Alerts;
end;
"""


def _dcn_events(n=240, keys=12, seed=21):
    rng = random.Random(seed)
    return [([f"dev{rng.randrange(keys)}",
              round(rng.uniform(0.0, 100.0), 2)], 1000 + i)
            for i in range(n)]


def _free_port():
    import socket
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_host_trace_stitching_survives_retry_and_takeover(tmp_path):
    """THE acceptance pin: one trace id with spans from both hosts
    including a ``dcn`` hop span, surviving a forced retry (lost-ack
    chaos → dedup) and a lane-group takeover (spill → survivor adopts →
    replay re-activates the contexts locally)."""
    from siddhi_tpu.resilience.chaos import ChaosInjector
    from siddhi_tpu.resilience.dcn_guard import (
        DCNGuardConfig, LaneGroupSnapshotStore)
    from siddhi_tpu.tpu.dcn import DCNWorker, LaneTopology

    store = LaneGroupSnapshotStore(str(tmp_path / "snaps"))
    chaos = ChaosInjector(seed=7, dcn_drop_p=0.3)    # lost acks → retries
    cfg = DCNGuardConfig(retry_max=10, retry_base_s=0.001,
                         retry_cap_s=0.01, failure_threshold=100)
    tr0 = PipelineTracer(sample_n=1, ring_size=256)
    tr1 = PipelineTracer(sample_n=1, ring_size=256)
    fl0 = FlightRecorder(capacity=128, app_name="w0")
    p0, p1 = _free_port(), _free_port()
    w1 = DCNWorker(1, LaneTopology(8, 2), DCN_APP, "dev", port=p1,
                   peers={0: ("127.0.0.1", p0)}, tracer=tr1,
                   snapshot_store=store, snapshot_every_frames=1)
    w0 = DCNWorker(0, LaneTopology(8, 2), DCN_APP, "dev", port=p0,
                   peers={1: ("127.0.0.1", p1)}, chaos=chaos,
                   guard_config=cfg, tracer=tr0, flight=fl0,
                   snapshot_store=store, snapshot_every_frames=1)
    try:
        # trace ids mint in per-host namespaces
        assert tr0.host == 0 and tr1.host == 1
        events = _dcn_events(240)
        half = len(events) // 2
        for i in range(0, half, 10):
            chunk = events[i:i + 10]
            w0.ingest([r for r, _ in chunk], [t for _, t in chunk])
        assert w1.dup_frames > 0, "no retry was deduped — chaos miswired?"

        # phase A evidence: a trace id recorded on host0 whose context was
        # adopted on host1, with a dcn hop span — ONE journey, two hosts
        ids0 = {t["trace_id"]: t for t in tr0.export()}
        stitched = [t for t in tr1.export() if t["trace_id"] in ids0]
        assert stitched, "no trace stitched across the DCN hop"
        for t in stitched:
            assert t["origin_host"] == 0 and t["host"] == 1
            hop = [s for s in t["spans"] if s["stage"] == "dcn"]
            assert hop and hop[0]["phase"] == "dcn_transit"
            assert hop[0]["duration_ms"] >= 0.0
        origin = ids0[stitched[0]["trace_id"]]
        assert {"ingress", "dcn"} <= {s["stage"] for s in origin["spans"]}

        # retried frames carried their context exactly once: every
        # stitched trace has at most one hop span per (sender) frame —
        # dedup means no double-adopted spans for the same frame
        for t in stitched:
            hops = [s for s in t["spans"]
                    if s["stage"] == "dcn" and s["name"] == "h0->h1"]
            assert len(hops) == 1

        # phase B: kill host1, spill, survivor takes the group over — the
        # replayed frames re-activate their contexts on host0
        w1.close()
        for i in range(half, len(events), 10):
            chunk = events[i:i + 10]
            w0.ingest([r for r, _ in chunk], [t for _, t in chunk])
        assert not w0.guard.spill(1).empty, "dead peer must spill"
        assert w0.take_over(1), "survivor takeover failed"
        # spill replay applied locally through the same dedup path and
        # stitched the spilled contexts back into their ORIGIN journeys:
        # one trace object carries both the ingress span and the hop
        adopted = [t for t in tr0.export()
                   if any(s["stage"] == "dcn" and s["name"] == "h0->h0"
                          for s in t["spans"])]
        assert adopted, "takeover replay dropped the trace contexts"
        for t in adopted:
            assert any(s["stage"] == "ingress" for s in t["spans"]), (
                "adopted hop span must land on the original journey")
        # control plane: the takeover is on the flight recorder
        kinds = [e["kind"] for e in fl0.export(category="dcn")]
        assert "takeover" in kinds
    finally:
        for w in (w0, w1):
            try:
                w.close()
            except OSError:
                pass


# ---------------------------------------------------------------------------
# overhead pin: tracing at default sampling + flight recorder armed
# ---------------------------------------------------------------------------

def _columnar_corpus(n=144_000, seed=11):
    # sized so a timed run lasts about 0.3 s: in shorter runs the noise of a
    # shared machine swamps the paired ratios
    rng = random.Random(seed)
    rows = [[f"s{rng.randrange(6)}", round(rng.uniform(0.0, 100.0), 3),
             rng.randrange(1000)] for _ in range(n)]
    tss = list(range(1_000_000, 1_000_000 + n))
    return rows, tss


def _columnar_run(manager, name, armed, rows, tss, chunk=512):
    text = (f"@app(name='{name}')\n"
            + ("@app:trace(sample='1/16')\n" if armed else "")
            + "@app:host_batch(batch='1024')\n"
            "define stream S (sym string, v double, n long);\n"
            "from S[v > 50.0] select sym, v insert into Out;")
    rt = manager.create_siddhi_app_runtime(text, playback=True)
    got = []
    rt.add_callback("Out", StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    ih = rt.input_handler("S")
    # warmup (compile/caches), then the timed corpus
    ih.send_rows([list(r) for r in rows[:chunk]], tss[:chunk])
    t0 = time.perf_counter()
    for s in range(0, len(rows), chunk):
        ih.send_rows([list(r) for r in rows[s:s + chunk]],
                     tss[s:s + chunk])
    rt.flush_host()
    dt = time.perf_counter() - t0
    evps = len(rows) / dt
    flight = rt.ctx.flight
    return evps, len(got), flight


def test_observability_overhead_pin_on_columnar_micro_corpus(manager):
    """Acceptance: the columnar micro-corpus with tracing at default
    sampling (1/16) + the always-on flight recorder armed runs within 5%
    of the disarmed throughput. Measured as PAIRED per-rep ratios with
    alternating order (armed-first on odd reps) so shared-machine noise —
    which dwarfs the microseconds of chunk-level sampling — cancels; the
    best paired ratio is the overhead estimate (a real ≥5% per-event cost
    would depress every pairing, noise only some)."""
    rows, tss = _columnar_corpus()
    ratios = []
    n_armed = n_plain = None
    flight = None
    for rep in range(4):
        if rep % 2 == 0:
            plain, n_plain, _ = _columnar_run(
                manager, f"pin_plain_{rep}", False, rows, tss)
            armed, n_armed, flight = _columnar_run(
                manager, f"pin_armed_{rep}", True, rows, tss)
        else:
            armed, n_armed, flight = _columnar_run(
                manager, f"pin_armed_{rep}", True, rows, tss)
            plain, n_plain, _ = _columnar_run(
                manager, f"pin_plain_{rep}", False, rows, tss)
        ratios.append(armed / plain)
    assert n_armed == n_plain, "observability changed outputs"
    assert max(ratios) >= 0.95, (
        f"armed/disarmed throughput ratios {[round(r, 3) for r in ratios]}"
        f" — observability overhead above 5% in every pairing")
    # the recorder stayed allocation-bounded in steady state: a bounded
    # ring, and no per-event recording (hot path records transitions only)
    assert len(flight.ring) <= flight.ring.maxlen
    assert flight.recorded <= 64


# ---------------------------------------------------------------------------
# the span-coverage lint gates from tier-1
# ---------------------------------------------------------------------------

def test_check_span_coverage_lint_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_span_coverage.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# flight recorder wiring: device quarantine timeline + HTTP endpoint
# ---------------------------------------------------------------------------

def test_device_quarantine_lands_on_flight_recorder(manager):
    rt = manager.create_siddhi_app_runtime(
        "@app(name='FRDev')\n"
        "@app:chaos(seed='3', device.fail.p='1.0')\n"
        "@app:resilience(device.circuit.threshold='2', "
        "device.circuit.cooldown.ms='60000')\n"
        "define stream S (v double);\n"
        "@device(batch='4') from S[v > 0.0] select v insert into Out;",
        playback=True)
    rt.start()
    ih = rt.input_handler("S")
    for i in range(12):
        ih.send([1.0 + i], timestamp=1000 + i)
    rt.flush_device()
    entries = rt.ctx.flight.export(category="device")
    kinds = [e["kind"] for e in entries]
    assert "step_failed" in kinds and "quarantined" in kinds
    breaker = [e for e in rt.ctx.flight.export(category="breaker")
               if e["site"] == "device:query-1"]
    assert any(e["kind"] == "circuit:open" for e in breaker)
    # entries are timestamp-ordered
    all_entries = rt.ctx.flight.export()
    assert [e["t"] for e in all_entries] == \
        sorted(e["t"] for e in all_entries)


def test_flightrecorder_since_ns_cursor():
    """Satellite pin (ISSUE 12): the ring is tailable incrementally — the
    SLO controller and external pollers pass the largest ``t_ns`` seen
    and get only newer transitions, loss-free (per-recorder ``t_ns`` is
    strictly increasing by construction)."""
    fr = FlightRecorder(capacity=64)
    for i in range(10):
        fr.record("flow", f"k{i}", site="s")
    entries = fr.export()
    t_ns = [e["t_ns"] for e in entries]
    assert t_ns == sorted(t_ns) and len(set(t_ns)) == 10, \
        "t_ns must be strictly increasing (the cursor contract)"
    cursor = entries[3]["t_ns"]
    tail = fr.export(since_ns=cursor)
    assert [e["kind"] for e in tail] == [f"k{i}" for i in range(4, 10)]
    # composes with category and limit
    fr.record("fleet", "ejected", site="s")
    assert [e["kind"] for e in fr.export(category="fleet",
                                         since_ns=cursor)] == ["ejected"]
    assert len(fr.export(since_ns=cursor, limit=2)) == 2
    # past-the-end cursor → empty page, and new records resume the tail
    end = fr.export()[-1]["t_ns"]
    assert fr.export(since_ns=end) == []
    fr.record("flow", "k10", site="s")
    assert [e["kind"] for e in fr.export(since_ns=end)] == ["k10"]


def test_flightrecorder_since_ns_http(manager):
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(manager, port=0)
    rt = manager.create_siddhi_app_runtime(
        "@app(name='FRTail')\n"
        "define stream S (v double);\n"
        "from S[v > 0.0] select v insert into Out;", playback=True)
    rt.start()
    svc.runtimes = {rt.name: rt}
    svc.start()
    try:
        for i in range(5):
            rt.ctx.flight.record("flow", f"k{i}", site="q")
        conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                          timeout=10)
        conn.request("GET", "/siddhi-apps/FRTail/flightrecorder")
        body = json.loads(conn.getresponse().read().decode())
        assert len(body["entries"]) == 5
        cursor = body["entries"][2]["t_ns"]
        conn.request("GET", "/siddhi-apps/FRTail/flightrecorder"
                     f"?since_ns={cursor}")
        body = json.loads(conn.getresponse().read().decode())
        assert [e["kind"] for e in body["entries"]] == ["k3", "k4"]
        # the incremental poll loop: nothing new → empty page
        cursor = body["entries"][-1]["t_ns"]
        conn.request("GET", "/siddhi-apps/FRTail/flightrecorder"
                     f"?since_ns={cursor}")
        body = json.loads(conn.getresponse().read().decode())
        assert body["entries"] == []
        conn.request("GET",
                     "/siddhi-apps/FRTail/flightrecorder?since_ns=bogus")
        assert conn.getresponse().status == 400
        conn.close()
    finally:
        svc.stop()


def test_flightrecorder_http_endpoint(manager):
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(manager, port=0)
    rt = manager.create_siddhi_app_runtime(
        "@app(name='FRHttp')\n"
        "define stream S (v double);\n"
        "from S[v > 0.0] select v insert into Out;", playback=True)
    rt.start()
    svc.runtimes = {rt.name: rt}
    svc.start()
    try:
        rt.ctx.flight.record("flow", "aimd_resize", site="q",
                             detail={"from": 128, "to": 64})
        rt.ctx.flight.record("fleet", "ejected", site="fleet:q")
        conn = http.client.HTTPConnection("127.0.0.1", svc.port,
                                          timeout=10)
        conn.request("GET", "/siddhi-apps/FRHttp/flightrecorder")
        body = json.loads(conn.getresponse().read().decode())
        assert body["enabled"] and len(body["entries"]) == 2
        conn.request("GET",
                     "/siddhi-apps/FRHttp/flightrecorder?category=fleet")
        body = json.loads(conn.getresponse().read().decode())
        assert [e["kind"] for e in body["entries"]] == ["ejected"]
        conn.request("GET",
                     "/siddhi-apps/FRHttp/flightrecorder?limit=1")
        body = json.loads(conn.getresponse().read().decode())
        assert len(body["entries"]) == 1
        conn.request("GET", "/siddhi-apps/Ghost/flightrecorder")
        assert conn.getresponse().status == 404
        conn.close()
    finally:
        svc.stop()


def test_phase_of_stage_total():
    # unknown stages classify as host work, never crash the export
    assert phase_of_stage("mystery") == "host_exec"
    for ph in PHASES:
        assert isinstance(ph, str)


# ---------------------------------------------------------------------------
# ISSUE 25: the driver thread's segments told apart (fence / decode, lock
# wait / publish, ring wait), as phase trackers and as profiler spans; the
# jitted stages named
# ---------------------------------------------------------------------------

SPLIT_APP = """
@app(name='Split')
define stream S (v double);
define stream O (v double, t double);
@info(name='agg')
@device(batch='64'{extra}) from S[v >= 0.0]#window.length(16)
select v, sum(v) as t insert into O;
"""


def _split_runtime(manager, async_mode):
    rt = manager.create_siddhi_app_runtime(
        SPLIT_APP.format(extra=", async='true'" if async_mode else ""),
        playback=True)
    rt.start()
    return rt, rt.device_bridges[0]


def _send(rt, n, start=0):
    ih = rt.input_handler("S")
    for i in range(start, start + n):
        ih.send([float(i)], timestamp=1000 + i)


def _assert_reconciles(rt, events):
    q = rt.observability.latency_report()["queries"]["agg"]
    assert q["end_to_end"]["count"] == events
    assert q["reconciliation_ratio"] == pytest.approx(1.0, abs=1e-5)
    return q


def test_the_three_new_phases_are_in_the_vocabulary():
    assert {"egress_decode", "lock_wait", "ring_wait"} <= set(PHASES)
    # the waterfall's order: a wait sits before the work it delays
    assert PHASES.index("egress_fence") < PHASES.index("egress_decode")
    assert PHASES.index("lock_wait") < PHASES.index("sink_publish")


@pytest.mark.parametrize("async_mode", [True, False], ids=["async", "sync"])
def test_collect_split_reconciles_on_both_paths(manager, async_mode):
    """Every batch's segments, the carved ones included, still sum to its
    end-to-end sample; fence and decode are both measured on every batch."""
    rt, bridge = _split_runtime(manager, async_mode)
    _send(rt, 64 * 3)         # fewer than the ring holds: no ring wait
    rt.flush_device()
    q = _assert_reconciles(rt, 64 * 3)
    trackers = bridge.probe.phases.trackers
    for phase in ("device_step", "egress_fence", "egress_decode"):
        assert trackers[phase].count == 64 * 3, phase
    assert trackers["ring_wait"].count == 0
    if async_mode:
        # step_seconds is dispatch + the whole of collect, so the three
        # trackers add up to it (what step.host_ms_per_batch reads)
        parts = sum(trackers[p].hist.sum for p in
                    ("device_step", "egress_fence", "egress_decode")) / 64
        assert parts == pytest.approx(bridge.driver.step_seconds, rel=1e-6)
        assert trackers["lock_wait"].count == trackers["sink_publish"].count \
            == 64 * 3
    else:
        # the client already holds the engine lock: no wait to measure
        assert trackers["lock_wait"].count == 0
    assert {"egress_fence", "egress_decode"} <= set(q["phases"])


def test_lock_wait_is_the_drivers_wait_for_the_engine_lock(manager):
    rt, bridge = _split_runtime(manager, True)
    drv, lock = bridge.driver, bridge.app_context.root_lock
    held_s = 0.08
    with lock:                          # a client inside a long send
        _send(rt, 64)                   # seals one batch (lock re-entered)
        deadline = time.monotonic() + 20.0
        while drv.batches_stepped < 1 and time.monotonic() < deadline:
            time.sleep(0.002)           # collected: the driver now wants
        assert drv.batches_stepped == 1     # the lock for its rows
        time.sleep(held_s)
    rt.flush_device()
    trackers = bridge.probe.phases.trackers
    assert trackers["lock_wait"].count == 64
    assert trackers["lock_wait"].hist.sum / 64 >= held_s * 0.9
    # the publishing itself was not charged for the wait
    assert trackers["sink_publish"].hist.sum / 64 < held_s / 2
    _assert_reconciles(rt, 64)


def test_ring_wait_is_the_clients_wait_on_a_full_ring(manager):
    rt, bridge = _split_runtime(manager, True)
    drv = bridge.driver
    drv.pause()
    try:
        _send(rt, 64 * (drv.depth + 1))     # the last seal finds it full
        assert drv.pipeline_depth == drv.depth + 1
    finally:
        drv.resume()
    rt.flush_device()
    trackers = bridge.probe.phases.trackers
    # one batch waited, for the whole 0.2 s (nothing woke the producer)
    assert trackers["ring_wait"].count == 64
    assert trackers["ring_wait"].hist.sum / 64 == pytest.approx(0.2, abs=0.05)
    # carved OUT of the queue wait, not counted twice
    _assert_reconciles(rt, 64 * (drv.depth + 1))


def test_a_guard_replay_is_neither_fence_nor_decode(manager):
    """A step whose collect fails after its fence is replayed on the host:
    the batch leaves nothing in the device-side trackers."""
    rt, bridge = _split_runtime(manager, True)
    compiled = bridge.runtime.compiled
    inner, left = compiled.decode_outputs, [1]

    def decode_once_broken(out):
        if left[0]:
            left[0] -= 1
            raise RuntimeError("sabotaged decode")
        return inner(out)

    compiled.decode_outputs = decode_once_broken
    _send(rt, 64 * 3)
    rt.flush_device()
    assert bridge.guard.failures == 1 and bridge.guard.fallback_events == 64
    trackers = bridge.probe.phases.trackers
    for phase in ("device_step", "egress_fence", "egress_decode"):
        assert trackers[phase].count == 64 * 2, phase
    _assert_reconciles(rt, 64 * 2)


SPAN_PARENTS = {"siddhi:collect.fence": "siddhi:collect",
                "siddhi:collect.decode": "siddhi:collect",
                "siddhi:deliver.lock": "siddhi:deliver",
                "siddhi:deliver.publish": "siddhi:deliver"}
CLIENT_SPANS = ("siddhi:seal.pack", "siddhi:submit.ring_wait")
DRIVER_SPANS = ("siddhi:dispatch", "siddhi:collect", "siddhi:deliver")


def test_profiler_trace_holds_every_span_nested_and_on_its_thread(
        manager, tmp_path):
    """No annotation, option or environment variable: an app that runs
    inside somebody's profiler session leaves its spans there."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    rt, bridge = _split_runtime(manager, True)
    _send(rt, 64)
    rt.flush_device()                   # compiled before the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("test:client"):
            bridge.driver.pause()
            try:                        # fill the ring: one ring_wait span
                _send(rt, 64 * (bridge.driver.depth + 1), start=64)
            finally:
                bridge.driver.resume()
        rt.flush_device()
        time.sleep(0.2)
    finally:
        jax.profiler.stop_trace()
    found = [p for p in tmp_path.rglob("*.xplane.pb")]
    assert found
    lines = []      # one per host thread: {span name: [(start, end), ...]}
    for plane in ProfileData.from_file(str(found[0])).planes:
        for line in plane.lines:
            spans = {}
            for ev in line.events:
                if ev.name.startswith(("siddhi:", "test:")):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
            if spans:
                lines.append(spans)
    q = ":agg"
    client = [ln for ln in lines if "test:client" in ln]
    driver = [ln for ln in lines if "siddhi:dispatch" + q in ln]
    assert len(client) == 1 and len(driver) == 1
    client, driver = client[0], driver[0]
    assert client is not driver
    for name in CLIENT_SPANS:
        assert name + q in client and name + q not in driver, name
    for name in DRIVER_SPANS + tuple(SPAN_PARENTS):
        assert name + q in driver and name + q not in client, name
    assert len(client["siddhi:submit.ring_wait" + q]) == 1
    (w0, w1), = client["siddhi:submit.ring_wait" + q]
    assert 0.15e9 <= w1 - w0 <= 0.4e9
    for child, parent in SPAN_PARENTS.items():
        for s, e in driver[child + q]:
            assert any(ps <= s and e <= pe for ps, pe in driver[parent + q]), \
                (child, s, e)
    # the children cover their parent but for clock reads: fence + decode
    # is collect, lock + publish is deliver
    for parent in ("siddhi:collect", "siddhi:deliver"):
        whole = sum(e - s for s, e in driver[parent + q])
        parts = sum(e - s for c, p in SPAN_PARENTS.items() if p == parent
                    for s, e in driver[c + q])
        assert 0.5 * whole <= parts <= whole, (parent, parts, whole)


SCOPED_PATTERN_APP = """
define stream S (dev string, v double);
@device(batch='64', slots='16')
from every a=S[v > 90.0] -> b=S[v > a.v] -> c=S[v > b.v] within 1000
select a.dev as d, c.v as v insert into M;
"""
SCOPED_STREAM_APP = """
define stream S (k int, v double);
@device(batch='64')
from S[v > 1.0]#window.length(16)
select k, v * 2.0 as d, sum(v) as t, avg(v) as m group by k having t > 0.0
insert into O;
"""


SCOPED_SCAN_APP = """
define stream S (dev string, v double);
@device(batch='64', slots='16')
from every a=S[v > 50.0] -> b=S[v > a.v]<3:> -> c=S[v < a.v] within 1000
select a.v as v1, b[0].v as first, b[last].v as peak, c.v as back
insert into M;
"""
SCOPED_HOPPING_APP = """
define stream S (k long, v long);
@device(batch='64', window='256')
from S#window.hopping(200, 40)
select k, count() as c, sum(v) as t group by k order by c desc, t limit 2
insert into O;
"""
# the blocked kernel's programs at the sizes of this file's fixtures, the
# benchmark's two chain queries (`pattern-chain8` plain, `partitioned-chain`
# under `vmap`): their optimized HLO on this backend as PR 34 left it (the
# emitted rows packed on the device; 2461 / 2431 at commit 8baf157)
CHAIN8 = ("from every e1=S[v > 50.0] -> e2=S[v > e1.v] -> e3=S[v > e2.v] "
          "-> e4=S[v > e3.v] -> e5=S[v > e4.v] -> e6=S[v > e5.v] "
          "-> e7=S[v > e6.v] -> e8=S[v > e7.v] within 4000\n"
          "select e1.v as v1, e2.v as v2, e3.v as v3, e4.v as v4, "
          "e5.v as v5, e6.v as v6, e7.v as v7, e8.v as v8 "
          "insert into Alerts;")
BLOCKED_PROGRAMS = {
    "pattern-chain8": (
        "define stream S (dev string, v double);\n"
        "@device(batch='64', slots='16')\n" + CHAIN8, 2790),
    "partitioned-chain": (
        "define stream S (dev string, v double);\n"
        "partition with (dev of S) begin\n"
        "@device(batch='256', slots='16', lanes='4')\n" + CHAIN8
        + "\nend;", 2757),
}


def _instructions(text):
    import re
    return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", text, re.M))


def _compiled_step_text(app_text):
    """Optimized HLO of the app's one jitted device step."""
    import numpy as np
    m = SiddhiManager()
    try:
        r = m.create_siddhi_app_runtime(
            app_text, playback=True).device_bridges[0].runtime
        b = r.builder.emit()
        if hasattr(r, "vstep"):         # a served partition: lanes stacked
            low = r.vstep.lower(r.state, *r._lay_out(b))
        elif hasattr(r, "compiler"):
            low = r.compiler._step.lower(
                r.state, b["cols"], b["tag"], b["ts"], b["ts_base"],
                np.int32(b["count"]))
        else:
            low = r.compiled._step.lower(r.state, b["cols"], b["ts"],
                                         b["valid"])
        return low.compile().as_text()
    finally:
        m.shutdown()


@pytest.mark.parametrize("app_text,scopes", [
    (SCOPED_PATTERN_APP, ("nfa.admit", "nfa.stage1", "nfa.stage2",
                          "nfa.emit", "nfa.compact")),
    (SCOPED_STREAM_APP, ("filter", "compact", "compact.keep", "compact.move",
                         "window.length", "groupby", "select")),
    (SCOPED_SCAN_APP, ("nfa.scan", "nfa.expire", "nfa.state0", "nfa.state1",
                       "nfa.state2", "nfa.emit", "nfa.compact")),
    # no filter: nothing is left in that scope
    (SCOPED_HOPPING_APP, ("compact", "compact.keep", "compact.move",
                          "window.hopping", "groupby.sort", "groupby.reduce",
                          "select.order", "select.limit")),
], ids=["nfa_block", "stream_query", "nfa_scan", "hopping_grouped"])
def test_jitted_stages_are_named_and_the_names_cost_no_operation(
        monkeypatch, app_text, scopes):
    import contextlib
    import re

    import jax

    instructions = _instructions

    named = _compiled_step_text(app_text)
    op_names = re.findall(r'op_name="([^"]*)"', named)
    for scope in scopes:
        assert any(scope in name.split("/") for name in op_names), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_step_text(app_text)
    assert not any(s in n.split("/") for s in scopes
                   for n in re.findall(r'op_name="([^"]*)"', bare))
    assert instructions(bare) == instructions(named) > 50


@pytest.mark.parametrize("name", list(BLOCKED_PROGRAMS))
def test_the_blocked_programs_are_the_programs_they_were(name):
    """A change to what the kernels share (the decode, the scan kernel's
    pack, the runtimes) must leave the blocked kernel's step the same
    program: the instruction count of its optimized HLO as PR 34 left it,
    which is PR 33's (2461 / 2431) plus the one pack of the emitted rows
    into the ``[B]`` row table and the count ``n``, and 94 more since the
    stage grids test `within` as an int32 delta against a limit each
    candidate computes once (its int64 sum and clip over ``[P]``, a stage:
    on this backend an int64 compare is one instruction, on a v5e a word
    pair's)."""
    app_text, pinned = BLOCKED_PROGRAMS[name]
    assert _instructions(_compiled_step_text(app_text)) == pinned
