"""Observability subsystem: percentile histograms, Prometheus exposition,
pipeline tracing, watermark lag, device-path probes, reporter races
(reference: Dropwizard statistics SPI; Hazelcast Jet's p99.99 argument for
percentile-first latency, arXiv:2103.10169)."""

import http.client
import importlib.util
import json
import os
import random
import subprocess
import sys
import threading
import time

import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.metrics import (
    GaugeTracker,
    LatencyTracker,
    Level,
    StatisticsManager,
)
from siddhi_tpu.observability import render
from siddhi_tpu.observability.histogram import LogHistogram
from siddhi_tpu.observability.tracing import PipelineTracer, parse_trace_annotation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- histogram

def _quantile(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def test_histogram_percentiles_match_reference_quantiles():
    rng = random.Random(7)
    h = LogHistogram()
    samples = [rng.lognormvariate(-7.0, 1.5) for _ in range(20_000)]
    for s in samples:
        h.record(s)
    assert h.count == len(samples)
    assert h.sum == pytest.approx(sum(samples))
    for q in (0.50, 0.90, 0.99, 0.999):
        est, ref = h.percentile(q), _quantile(samples, q)
        # the geometric ladder guarantees ref < est <= ref * growth
        assert ref <= est <= ref * h.growth * 1.01, (q, est, ref)
    assert h.min == pytest.approx(min(samples))
    assert h.max == pytest.approx(max(samples))


def test_histogram_buckets_are_cumulative_and_bounded():
    h = LogHistogram()
    for v in (1e-6, 1e-4, 1e-4, 5.0):
        h.record(v)
    buckets = h.buckets()
    assert all(b1 <= b2 for (_, b1), (_, b2) in zip(buckets, buckets[1:]))
    assert buckets[-1][1] == h.count
    # ladder is trimmed: far fewer lines than the full 128-bucket ladder
    assert len(buckets) < 128


def test_histogram_overflow_and_garbage_samples():
    h = LogHistogram()
    h.record(1e9)              # far past the ladder: overflow bucket
    h.record(-3.0)             # negative clamps to 0
    h.record(float("nan"))     # NaN clamps to 0
    assert h.count == 3
    assert h.percentile(1.0) == h.max


# -------------------------------------------------------- latency tracker

def test_latency_tracker_token_api_overlapping_measurements():
    t = LatencyTracker("x")
    a = t.start()
    b = t.start()              # overlapping: the single-slot API mis-paired
    t.stop(b)
    t.stop(a)
    assert t.count == 2
    assert t.avg_ms >= 0.0
    p = t.percentiles_ms()
    assert p["count"] == 2 and p["p99_ms"] >= p["p50_ms"] >= 0.0


def test_latency_tracker_concurrent_threads_drop_no_samples():
    t = LatencyTracker("x")
    n_threads, per_thread = 8, 200

    def work():
        for _ in range(per_thread):
            tok = t.start()
            t.stop(tok)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.count == n_threads * per_thread


def test_latency_tracker_mark_in_out_shim_removed():
    # the deprecated single-slot shim is gone (PR 10): every measurement
    # pairs through explicit tokens, so overlapping sites can't mis-pair
    t = LatencyTracker("legacy")
    assert not hasattr(t, "mark_in")
    assert not hasattr(t, "mark_out")
    tok = t.start()
    t.stop(tok)
    assert t.count == 1
    assert t.total_ns >= 0


def test_latency_tracker_weighted_and_exemplar_records():
    t = LatencyTracker("weighted")
    t.record_seconds(0.010, n=8, exemplar=41)
    assert t.count == 8
    assert abs(t.hist.sum - 0.08) < 1e-9
    ex = t.hist.exemplars()
    assert len(ex) == 1
    (le, (tid, value, ts)), = ex.items()
    assert tid == "41" and abs(value - 0.010) < 1e-12 and value <= le
    # no exemplar → no allocation, empty map
    t2 = LatencyTracker("bare")
    t2.record_seconds(0.010)
    assert t2.hist.exemplars() == {} and t2.hist._exemplars is None


# ------------------------------------------------------------ dead gauges

def test_dead_gauge_counts_errors_and_logs_once(caplog):
    sm = StatisticsManager("app")

    def boom():
        raise RuntimeError("probe detached")

    g = sm.gauge_tracker("flow.S.wal_bytes", boom)
    with caplog.at_level("WARNING", logger="siddhi_tpu.metrics"):
        assert g.value == 0
        assert g.value == 0
    assert sm.gauge_errors.count == 2
    warned = [r for r in caplog.records if "wal_bytes" in r.getMessage()]
    assert len(warned) == 1                 # once per gauge, not per read
    # report() itself evaluates the dead gauge once more → 3
    assert sm.report()["counters"]["app.gauge_errors"] == 3


def test_healthy_gauge_has_no_errors():
    g = GaugeTracker("x", lambda: 7)
    assert g.value == 7


# --------------------------------------------------- manager thread-safety

def test_registration_during_report_does_not_race():
    sm = StatisticsManager("app")
    sm.set_level(Level.BASIC)
    stop = threading.Event()
    errors = []

    def register_loop():
        # bounded: enough inserts to overlap the report loop's iterations
        # (pre-fix this raised "dictionary changed size during iteration")
        # without growing render() quadratically forever
        for i in range(3000):
            if stop.is_set():
                return
            sm.gauge_tracker(f"stream.S{i}.depth", lambda: 0)
            sm.counter_tracker(f"stream.S{i}.drops_total")
            sm.latency_tracker(f"query.q{i}")

    def report_loop():
        try:
            for _ in range(60):
                sm.report()
                render([sm])
        except RuntimeError as e:           # "dict changed size" pre-fix
            errors.append(e)

    reg = threading.Thread(target=register_loop)
    rep = threading.Thread(target=report_loop)
    reg.start()
    rep.start()
    rep.join()
    stop.set()
    reg.join()
    assert not errors


def test_reporter_start_stop_race_leaves_no_timer():
    calls = []

    class Capture:
        def report(self, data):
            calls.append(data)

    sm = StatisticsManager("x")
    sm.set_level(Level.BASIC)
    sm.reporter = Capture()
    sm.report_interval_s = 0.01

    def churn():
        for _ in range(20):
            sm.start_reporting()
            sm.stop_reporting()

    threads = [threading.Thread(target=churn) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    sm.stop_reporting()
    assert sm._timer is None
    time.sleep(0.05)                        # let in-flight ticks finish
    n = len(calls)
    time.sleep(0.15)                        # ≫ interval: a surviving chain
    assert len(calls) == n                  # would have reported again


# ----------------------------------------------------------- trace spans

TRACED_APP = """
@app(name='Traced', statistics='true')
@app:trace(sample='1/1')
define stream S (v long);
@sink(type='inMemory', topic='obs_traced', @map(type='passThrough'))
define stream O (t long);
from S[v >= 0]#window.lengthBatch(2) select sum(v) as t insert into O;
"""


def test_trace_spans_cross_filter_window_sink():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(TRACED_APP, playback=True)
    got = []
    rt.add_callback("O", StreamCallback(lambda evs: got.extend(e.data for e in evs)))
    rt.start()
    ih = rt.input_handler("S")
    ih.send([1], timestamp=1000)
    ih.send([2], timestamp=2000)
    assert got == [[3]]
    export = rt.observability.trace_export()
    assert export["enabled"] and len(export["traces"]) == 2
    # the batch-closing event crosses every stage
    closing = export["traces"][1]
    stages = {s["stage"] for s in closing["spans"]}
    assert {"ingress", "query", "window", "selector", "sink"} <= stages
    assert all(s["duration_ms"] >= 0 for s in closing["spans"])
    sink_span = next(s for s in closing["spans"] if s["stage"] == "sink")
    assert sink_span["outcome"] == "published"
    # end-to-end query latency histogram recorded alongside
    q = rt.ctx.statistics_manager.latency["query.query-1"]
    assert q.count == 2
    m.shutdown()


def test_trace_sampling_one_in_n():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
    @app(name='Sampled')
    @app:trace(sample='1/4', ring='8')
    define stream S (v long);
    from S select v insert into O;
    """, playback=True)
    rt.start()
    ih = rt.input_handler("S")
    for i in range(16):
        ih.send([i], timestamp=1000 + i)
    export = rt.observability.trace_export()
    assert len(export["traces"]) == 4       # 16 events, 1-in-4
    m.shutdown()


def test_trace_rides_async_junction_to_worker_thread():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
    @app(name='AsyncTraced', statistics='true')
    @app:trace(sample='1/1')
    @async(buffer.size='64')
    define stream S (v long);
    from S select v insert into O;
    """, playback=True)
    rt.add_callback("O", StreamCallback(lambda evs: None))
    rt.start()
    ih = rt.input_handler("S")
    for i in range(8):
        ih.send([i], timestamp=1000 + i)
    rt.drain_async()
    export = rt.observability.trace_export()
    with_query = [t for t in export["traces"]
                  if "query" in {s["stage"] for s in t["spans"]}]
    assert with_query, "no query spans recorded on the async worker"
    m.shutdown()


def test_trace_annotation_parsing():
    from siddhi_tpu.query_api.annotation import Annotation
    ann = Annotation("trace").element("sample", "1/32").element("ring", "64")
    tr = parse_trace_annotation(ann)
    assert tr.sample_n == 32 and tr.ring.maxlen == 64
    with pytest.raises(ValueError):
        parse_trace_annotation(Annotation("trace").element("sample", "3/4"))
    from siddhi_tpu.core.errors import SiddhiAppCreationError
    with pytest.raises(SiddhiAppCreationError):
        SiddhiManager().create_siddhi_app_runtime("""
        @app:trace(sample='2/3')
        define stream S (v long);
        from S select v insert into O;
        """)


def test_tracer_ring_is_bounded():
    tr = PipelineTracer(sample_n=1, ring_size=4)
    for _ in range(10):
        tr.maybe_trace("S")
    assert len(tr.ring) == 4


# -------------------------------------------------------- watermark lag

def test_watermark_lag_gauge_under_playback():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
    @app(name='WM', statistics='true')
    define stream S (v long);
    define stream T (v long);
    from S select v insert into O;
    from T select v insert into O;
    """, playback=True)
    rt.add_callback("O", StreamCallback(lambda evs: None))
    rt.start()
    rt.input_handler("S").send([1], timestamp=1000)
    rt.input_handler("T").send([1], timestamp=4000)
    # T's event advanced the app clock to 4000; S last saw 1000 → 3s behind
    gauges = rt.ctx.statistics_manager.gauges
    assert gauges["stream.S.watermark_lag_seconds"].value == pytest.approx(3.0)
    assert gauges["stream.T.watermark_lag_seconds"].value == pytest.approx(0.0)
    rt.advance_time(6000)
    assert gauges["stream.S.watermark_lag_seconds"].value == pytest.approx(5.0)
    assert gauges["stream.S.events_total"].value == 1
    m.shutdown()


# ------------------------------------------------------- device probes

def test_device_step_probe_counts_and_histogram():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
    @app(name='Dev', statistics='true')
    @app:trace(sample='1/1')
    define stream S (v double);
    @device(batch='32')
    from S#window.length(16) select sum(v) as t insert into O;
    """, playback=True)
    rt.add_callback("O", StreamCallback(lambda evs: None))
    rt.start()
    assert rt.device_bridges
    probe = rt.device_bridges[0].probe
    assert probe is not None
    ih = rt.input_handler("S")
    for i in range(40):                     # 32 fill a batch, 8 remain
        ih.send([float(i)], timestamp=1000 + i)
    rt.flush_device()
    assert probe.steps >= 2
    assert probe.events == 40
    assert 0.0 <= probe.pad_ratio < 1.0
    assert probe.first_step_seconds > 0
    assert probe.flush_causes.get("capacity", 0) >= 1
    assert probe.flush_causes.get("drain", 0) >= 1
    sm = rt.ctx.statistics_manager
    q = rt.device_bridges[0].query_name
    assert sm.latency[f"device.{q}.step"].count == probe.steps
    assert sm.gauges[f"device.{q}.steps_total"].value == probe.steps
    # traced events closed device spans
    export = rt.observability.trace_export()
    dev_spans = [s for t in export["traces"] for s in t["spans"]
                 if s["stage"] == "device"]
    assert dev_spans and all(s["duration_ms"] >= 0 for s in dev_spans)
    m.shutdown()
    assert probe.flush_causes.get("final", 0) >= 0   # shutdown path ran


# --------------------------------------------------- prometheus rendering

def _parse_samples(text):
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        out.setdefault(name, []).append(line)
    return out


def test_prometheus_exposition_format_and_p99_derivable():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(TRACED_APP, playback=True)
    rt.add_callback("O", StreamCallback(lambda evs: None))
    rt.start()
    ih = rt.input_handler("S")
    for i in range(10):
        ih.send([i], timestamp=1000 + i)
    text = render([rt.ctx.statistics_manager])
    m.shutdown()

    # structural lint (the same checker CI runs)
    spec = importlib.util.spec_from_file_location(
        "check_metric_names", os.path.join(REPO, "scripts",
                                           "check_metric_names.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.check(text) == []

    samples = _parse_samples(text)
    assert "siddhi_tpu_stream_events_total" in samples
    assert "siddhi_tpu_sink_publish_latency_seconds_bucket" in samples
    # p99 derivable: walk query-latency buckets to the 99th percentile rank
    buckets = []
    for line in samples["siddhi_tpu_query_latency_seconds_bucket"]:
        labels, value = line.rsplit(" ", 1)
        le = labels.split('le="')[1].split('"')[0]
        buckets.append((float("inf") if le == "+Inf" else float(le),
                        float(value)))
    buckets.sort(key=lambda x: x[0])
    total = buckets[-1][1]
    assert total == 10.0
    p99_bound = next(le for le, cum in buckets if cum >= 0.99 * total)
    assert 0 < p99_bound < float("inf")
    # labels carry app and query
    assert 'app="Traced"' in samples["siddhi_tpu_query_latency_seconds_count"][0]
    assert 'query="query-1"' in samples["siddhi_tpu_query_latency_seconds_count"][0]


def test_check_metric_names_lint_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_metric_names.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr


def test_check_metric_names_catches_offenders():
    spec = importlib.util.spec_from_file_location(
        "check_metric_names", os.path.join(REPO, "scripts",
                                           "check_metric_names.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    bad = "\n".join([
        "# TYPE siddhi_tpu_x gauge",
        "# TYPE not_prefixed gauge",          # bad prefix
        'siddhi_tpu_x{app="a"} 1',
        'siddhi_tpu_x{app="a"} 2',            # duplicate sample
        'siddhi_tpu_orphan{app="a"} 1',       # no TYPE
    ])
    problems = lint.check(bad)
    assert len(problems) == 3


# ------------------------------------------------------- service endpoints

@pytest.fixture
def service():
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(playback=True)
    svc.start()
    yield svc
    svc.stop()


def _get(svc, path):
    conn = http.client.HTTPConnection("127.0.0.1", svc.port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read().decode()
    ctype = resp.getheader("Content-Type")
    conn.close()
    return resp.status, ctype, body


def test_service_metrics_and_trace_endpoints(service):
    code, _ = service.deploy(TRACED_APP)
    assert code == 200
    rt = service.runtimes["Traced"]
    ih = rt.input_handler("S")
    for i in range(4):
        ih.send([i], timestamp=1000 + i)

    code, ctype, body = _get(service, "/siddhi-apps/Traced/metrics")
    assert code == 200 and ctype.startswith("text/plain")
    assert "version=0.0.4" in ctype
    assert "siddhi_tpu_query_latency_seconds_bucket" in body
    assert 'le="+Inf"' in body

    code, ctype, body = _get(service, "/metrics")       # all-apps scrape
    assert code == 200 and 'app="Traced"' in body

    code, _, body = _get(service, "/siddhi-apps/Traced/trace?limit=2")
    assert code == 200
    payload = json.loads(body)
    assert payload["enabled"] and len(payload["traces"]) == 2
    stages = {s["stage"] for t in payload["traces"] for s in t["spans"]}
    assert {"ingress", "query", "window", "sink"} <= stages

    code, _, _ = _get(service, "/siddhi-apps/Ghost/metrics")
    assert code == 404
    code, _, _ = _get(service, "/siddhi-apps/Ghost/trace")
    assert code == 404


def test_quarantined_device_steps_still_drain_trace_groups():
    """During a device quarantine the guard reroutes steps to the host
    path; traced events' device spans must still close (outcome
    'fallback') instead of piling up in the probe, and fallback timings
    must not pollute the device-step histogram."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
    @app(name='Chaos', statistics='true')
    @app:trace(sample='1/1')
    @app:chaos(seed='7', device.fail.p='1.0')
    define stream S (v double);
    @device(batch='4')
    from S[v >= 0] select v as t insert into O;
    """, playback=True)
    got = []
    rt.add_callback("O", StreamCallback(lambda evs: got.extend(e.data for e in evs)))
    rt.start()
    assert rt.device_bridges
    probe = rt.device_bridges[0].probe
    ih = rt.input_handler("S")
    for i in range(12):                     # 3 full batches, all steps fail
        ih.send([float(i)], timestamp=1000 + i)
    rt.flush_device()
    assert len(got) == 12                   # host fallback: zero event loss
    assert not probe.pending and not probe._groups   # nothing accumulates
    assert probe.steps == 0                 # no DEVICE step succeeded
    sm = rt.ctx.statistics_manager
    q = rt.device_bridges[0].query_name
    assert sm.latency[f"device.{q}.step"].count == 0
    dev_spans = [s for t in rt.observability.tracer.export()
                 for s in t["spans"] if s["stage"] == "device"]
    assert dev_spans and all(s["outcome"] == "fallback" for s in dev_spans)
    m.shutdown()
