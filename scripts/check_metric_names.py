#!/usr/bin/env python
"""Repo lint: the Prometheus exposition surface stays well-formed.

Deploys a representative app exercising every metric family (async
streams, flow control, device offload, resilient sinks, latency
histograms), renders the exposition, and enforces:

- every metric name is ``snake_case`` and ``siddhi_tpu``-prefixed;
- every label name is ``snake_case`` and every sample line parses;
- each (metric, labels) sample appears exactly once per app — a tracker
  registered twice per app would double-expose here;
- ``# TYPE`` is declared exactly once per family, before its samples;
- histogram bucket counts are cumulative (monotone, ``+Inf`` == count);
- OpenMetrics exemplars (`` # {trace_id="..."} value ts``) appear ONLY on
  histogram ``_bucket`` samples, parse, carry a bounded label set
  (``trace_id`` only, ≤ 128 runes total per the OpenMetrics spec), and
  their value lies within the bucket's ``le`` bound;
- label cardinality stays bounded: per family no label fans out past
  ``MAX_LABEL_VALUES`` distinct values, and unbounded-identity label
  names (``tenant``/``user``/``trace_id``/...) never appear as labels —
  per-tenant families must aggregate or exemplar-link, not explode the
  time-series space;
- the SLO-autopilot families (``siddhi_tpu_slo_*``, exercised by a fleet
  tenant with declared ``slo.*`` keys in the lint deployment) carry ONLY
  the ``app``/``query`` label set — compliance is per tenant query, and a
  tenant query is already app-scoped, so any further label would be an
  identity in disguise;
- the mesh-fabric families (``siddhi_tpu_mesh_*``, exercised by a small
  two-host fabric the lint spins up and registers onto the main app's
  statistics manager) render on every run and carry ONLY the
  ``app``/``host`` label set — host indices are bounded by the mesh size
  (≤ 255, the DCN wire bound), tenant identities stay in report payloads;
- the federated exposition (ISSUE 18, exercised by a two-host PROCESS
  fabric whose ``collect_federated`` hook renders scraped per-worker
  families): every ``worker`` label value comes from the bounded
  vocabulary ``h{i}``/``w{i}``/``fabric``/``recovery``/``self`` (never a
  free-form identity — cardinality is mesh-size-bounded by shape, not by
  luck), federated histograms pass the same cumulative-``le`` checks as
  native ones, and no federated sample collides with a parent-side
  sample of the same family once its ``worker`` label is stripped (a
  collision would make parent and child series indistinguishable under
  aggregation).

Usage: ``python scripts/check_metric_names.py``. Exit code 1 on findings.
Run by ``tests/test_observability.py`` so it gates CI (the
``check_excepts.py`` pattern).
"""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable as `python scripts/check_metric_names.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

METRIC_RE = re.compile(r"^siddhi_tpu_[a-z][a-z0-9_]*$")
LABEL_RE = re.compile(r"^[a-z_][a-z0-9_]*$")
SAMPLE_RE = re.compile(
    r"^(?P<name>[A-Za-z_:][A-Za-z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)"
    r"(?P<exemplar> # \{[^}]*\} \S+(?: \S+)?)?$")
EXEMPLAR_RE = re.compile(
    r"^ # \{(?P<labels>[^}]*)\} (?P<value>\S+)(?: (?P<ts>\S+))?$")
LABEL_PAIR_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')

# identity-shaped label names that would make a family's cardinality grow
# with the user population — these belong in exemplars or report payloads
FORBIDDEN_LABELS = {"tenant", "tenant_id", "user", "user_id", "trace_id",
                    "session", "session_id", "event_id"}
# per-family distinct-value bound per label within one exposition
MAX_LABEL_VALUES = 64
# OpenMetrics: exemplar label set must stay under 128 runes
MAX_EXEMPLAR_RUNES = 128
EXEMPLAR_LABELS = {"trace_id"}
# slo.* compliance families: per tenant query, nothing finer
SLO_LABELS = {"app", "query"}
# mesh.* fabric families: per host (bounded by mesh size), nothing finer
MESH_LABELS = {"app", "host"}
# worker label values: index-shaped or one of the reserved series — a
# free-form value here is an identity leaking into the time-series space
WORKER_VALUE_RE = re.compile(r"^(h\d+|w\d+|fabric|recovery|self)$")

APP = """
@app(name='LintApp', statistics='detail')
@app:backpressure(capacity='64', policy='shed')
@app:trace(sample='1/1')
@async(buffer.size='32')
define stream S (v double);
@sink(type='inMemory', topic='lint_t', @map(type='passThrough'))
define stream O (t double);
@device(batch='32')
from S#window.length(16) select sum(v) as t insert into O;
"""

# a fleet tenant with declared SLO keys: the siddhi_tpu_slo_* compliance
# families render, so their naming/label discipline is linted on every run
SLO_APP = """
@app(name='LintSlo', statistics='true')
@app:fleet(batch='64', slo.p99.ms='50', slo.class='premium')
define stream F (sym string, v double);
@info(name='fq')
from F[v > 1.0] select sym, v insert into FO;
"""

# a served keyed window (PR 39): the phase of its key lookup and the gauges
# of its table render, so their names are linted on every run
KEYED_APP = """
@app(name='LintKeyed', statistics='true')
define stream K (dev long, v double);
partition with (dev of K) begin
@device(batch='16', keys='8')
from K#window.length(4) select dev, max(v) as m insert into KO;
end;
"""


MESH_TENANT = """
@app(name='lint-mesh-{i}')
@app:fleet(batch='64')
define stream S (sym string, v double);
from S[v > 1.0] select sym, v insert into MO;
"""


def build_exposition() -> str:
    import tempfile

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.mesh import MeshConfig, MeshFabric
    from siddhi_tpu.observability import render

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP, playback=True)
    rt.start()
    srt = m.create_siddhi_app_runtime(SLO_APP, playback=True)
    srt.start()
    ih = rt.input_handler("S")
    for i in range(40):
        ih.send([float(i)], timestamp=1000 + i)
    fh = srt.input_handler("F")
    for i in range(20):
        fh.send([f"s{i % 3}", float(i)], timestamp=1000 + i)
    krt = m.create_siddhi_app_runtime(KEYED_APP, playback=True)
    krt.start()
    kh = krt.input_handler("K")
    for i in range(40):
        kh.send([(i % 3) << 40, float(i)], timestamp=1000 + i)
    krt.flush_device()
    rt.drain_async()
    rt.flush_device()
    srt.flush_host()
    # a two-host mesh fabric registered onto the main app's statistics
    # manager: the siddhi_tpu_mesh_* families render (and get linted for
    # naming + the bounded {app, host} label set) on every run
    mesh = MeshFabric(2, tempfile.mkdtemp(prefix="lint-mesh-"),
                      MeshConfig(capacity_per_host=4))
    mesh.add_tenants([MESH_TENANT.format(i=i) for i in range(2)])
    mesh.send("lint-mesh-0", "S", [["a", 2.0], ["b", 3.0]], [1000, 1001])
    mesh.flush()
    mesh.register_metrics(rt.ctx.statistics_manager)
    # a two-host PROCESS fabric with trace sampling: its federated
    # collector renders scraped per-worker + fabric-merged families, so
    # the worker-label vocabulary, federated le-bucket structure and
    # parent/child collision rules are linted on every run (ISSUE 18)
    pmesh = MeshFabric(2, tempfile.mkdtemp(prefix="lint-pmesh-"),
                       MeshConfig(capacity_per_host=1, mode="process",
                                  trace_sample=1))
    pmesh.add_tenants([MESH_TENANT.format(i=i + 2) for i in range(2)])
    for i in range(2):
        pmesh.send(f"lint-mesh-{i + 2}", "S",
                   [["a", 2.0], ["b", 3.0]], [1000, 1001])
    pmesh.flush()
    pmesh.sync_children()
    # the OpenMetrics-flavored exposition: exemplars present, so their
    # syntax/placement/bounds are exercised by every lint run
    text = render([rt.ctx.statistics_manager,
                   srt.ctx.statistics_manager], with_exemplars=True,
                  collectors=(pmesh.collect_federated,))
    pmesh.close()
    mesh.close()
    m.shutdown()
    return text


def _check_exemplar(lineno: int, name: str, family: str, typed: dict,
                    labels: dict, raw_ex: str, problems: list) -> None:
    """Exemplar syntax + placement + bound lint for one sample line."""
    if typed.get(family) != "histogram" or not name.endswith("_bucket"):
        problems.append(
            f"line {lineno}: exemplar on non-bucket sample '{name}' — "
            f"exemplars attach to histogram le buckets only")
        return
    m = EXEMPLAR_RE.match(raw_ex)
    if m is None:
        problems.append(f"line {lineno}: malformed exemplar: {raw_ex!r}")
        return
    ex_labels = {}
    raw = m.group("labels")
    consumed = sum(len(p.group(0)) for p in LABEL_PAIR_RE.finditer(raw))
    if len(raw.replace(",", "")) != consumed:
        problems.append(
            f"line {lineno}: malformed exemplar labels: {{{raw}}}")
    for p in LABEL_PAIR_RE.finditer(raw):
        ex_labels[p.group(1)] = p.group(2)
    extra = set(ex_labels) - EXEMPLAR_LABELS
    if extra:
        problems.append(
            f"line {lineno}: exemplar labels {sorted(extra)} — only "
            f"{sorted(EXEMPLAR_LABELS)} may ride an exemplar")
    runes = sum(len(k) + len(v) for k, v in ex_labels.items())
    if runes > MAX_EXEMPLAR_RUNES:
        problems.append(
            f"line {lineno}: exemplar label set is {runes} runes "
            f"(OpenMetrics bound: {MAX_EXEMPLAR_RUNES})")
    try:
        ex_value = float(m.group("value"))
    except ValueError:
        problems.append(
            f"line {lineno}: non-numeric exemplar value "
            f"{m.group('value')!r}")
        return
    if m.group("ts") is not None:
        try:
            float(m.group("ts"))
        except ValueError:
            problems.append(
                f"line {lineno}: non-numeric exemplar timestamp "
                f"{m.group('ts')!r}")
    le = labels.get("le")
    if le is not None and le != "+Inf" and ex_value > float(le) * 1.0001:
        problems.append(
            f"line {lineno}: exemplar value {ex_value} exceeds its "
            f"bucket's le={le}")


def check(text: str) -> list[str]:
    problems: list[str] = []
    typed: dict[str, str] = {}
    seen_samples: set[tuple] = set()
    histograms: dict[tuple, list[tuple[float, float]]] = {}
    hist_counts: dict[tuple, float] = {}
    label_values: dict[tuple, set] = {}   # (family, label) -> value set
    # parent/child collision ledger: federated samples with the worker
    # label stripped vs parent-side samples of the same family
    fed_stripped: dict[tuple, int] = {}   # (name, labels-sans-worker) -> line
    parent_keys: dict[tuple, int] = {}    # (name, labels) -> line

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            name, mtype = parts[2], parts[3]
            if not METRIC_RE.match(name):
                problems.append(
                    f"line {lineno}: metric '{name}' is not snake_case "
                    f"siddhi_tpu_*")
            if name in typed:
                problems.append(
                    f"line {lineno}: duplicate TYPE for '{name}'")
            typed[name] = mtype
            continue
        if line.startswith("#"):
            problems.append(f"line {lineno}: unknown comment form: {line}")
            continue
        m = SAMPLE_RE.match(line)
        if m is None:
            problems.append(f"line {lineno}: unparseable sample: {line}")
            continue
        name = m.group("name")
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        if base not in typed and name not in typed:
            problems.append(
                f"line {lineno}: sample '{name}' has no TYPE declaration "
                f"above it")
            base = name
        family = base if base in typed else name
        labels = {}
        raw = m.group("labels") or ""
        consumed = sum(len(p.group(0)) for p in LABEL_PAIR_RE.finditer(raw))
        if len(raw.replace(",", "")) != consumed:
            problems.append(f"line {lineno}: malformed labels: {{{raw}}}")
        for p in LABEL_PAIR_RE.finditer(raw):
            k, v = p.group(1), p.group(2)
            if not LABEL_RE.match(k):
                problems.append(
                    f"line {lineno}: label '{k}' is not snake_case")
            if k in FORBIDDEN_LABELS:
                problems.append(
                    f"line {lineno}: label '{k}' is an unbounded identity "
                    f"— per-tenant families must carry bounded label sets")
            labels[k] = v
            if k != "le":
                label_values.setdefault((family, k), set()).add(v)
        if family.startswith("siddhi_tpu_slo_"):
            extra = set(labels) - SLO_LABELS - {"le"}
            if extra:
                problems.append(
                    f"line {lineno}: slo family '{family}' carries labels "
                    f"{sorted(extra)} — compliance families allow only "
                    f"{sorted(SLO_LABELS)}")
        if family.startswith("siddhi_tpu_mesh_"):
            extra = set(labels) - MESH_LABELS - {"le"}
            if extra:
                problems.append(
                    f"line {lineno}: mesh family '{family}' carries labels "
                    f"{sorted(extra)} — fabric families allow only "
                    f"{sorted(MESH_LABELS)}")
        if m.group("exemplar"):
            _check_exemplar(lineno, name, family, typed, labels,
                            m.group("exemplar"), problems)
        try:
            value = float(m.group("value"))
        except ValueError:
            problems.append(
                f"line {lineno}: non-numeric value {m.group('value')!r}")
            continue
        key = (name, tuple(sorted(labels.items())))
        if key in seen_samples:
            problems.append(
                f"line {lineno}: duplicate sample {name}{dict(labels)} — "
                f"a metric must be registered exactly once per app")
        seen_samples.add(key)
        # federated worker-label discipline + collision ledger (ISSUE 18)
        worker = labels.get("worker")
        if worker is not None:
            if not WORKER_VALUE_RE.match(worker):
                problems.append(
                    f"line {lineno}: worker label value '{worker}' is not "
                    f"index-shaped (h<i>/w<i>) or a reserved series — "
                    f"free-form worker values are unbounded identities")
            stripped = (name, tuple(sorted(
                (k, v) for k, v in labels.items() if k != "worker")))
            fed_stripped.setdefault(stripped, lineno)
        else:
            parent_keys.setdefault(key, lineno)
        # histogram structure
        if typed.get(family) == "histogram":
            series = tuple(sorted((k, v) for k, v in labels.items()
                                  if k != "le"))
            if name.endswith("_bucket"):
                le = labels.get("le")
                b = float("inf") if le == "+Inf" else float(le)
                histograms.setdefault((family, series), []).append((b, value))
            elif name.endswith("_count"):
                hist_counts[(family, series)] = value

    for (family, series), buckets in histograms.items():
        buckets.sort(key=lambda x: x[0])
        last = -1.0
        for le, cum in buckets:
            if cum < last:
                problems.append(
                    f"{family}{dict(series)}: bucket le={le} count {cum} "
                    f"not cumulative")
            last = cum
        if buckets and buckets[-1][0] != float("inf"):
            problems.append(f"{family}{dict(series)}: missing +Inf bucket")
        total = hist_counts.get((family, series))
        if buckets and total is not None and buckets[-1][1] != total:
            problems.append(
                f"{family}{dict(series)}: +Inf bucket {buckets[-1][1]} "
                f"!= _count {total}")
    from siddhi_tpu.observability.phases import SEGMENTS, THREAD_CLOCKS
    for (family, label), values in label_values.items():
        if len(values) > MAX_LABEL_VALUES:
            problems.append(
                f"{family}: label '{label}' has {len(values)} distinct "
                f"values (bound {MAX_LABEL_VALUES}) — cardinality must not "
                f"scale with population")
        if label == "phase":
            # one vocabulary: a phase label outside observability.phases
            # SEGMENTS is a tracker somebody named by hand
            stray = values - set(SEGMENTS) - set(THREAD_CLOCKS) \
                - {"end_to_end"}
            if stray:
                problems.append(
                    f"{family}: phase label values {sorted(stray)} are "
                    f"not in observability.phases.SEGMENTS")
    # parent/child collision: a federated sample that equals a parent
    # sample once its worker label is stripped would make the two series
    # indistinguishable under sum()/avg() aggregation over workers
    for stripped, lineno in fed_stripped.items():
        if stripped in parent_keys:
            name, labels = stripped
            problems.append(
                f"line {lineno}: federated sample {name}{dict(labels)} "
                f"collides with the parent-side sample at line "
                f"{parent_keys[stripped]} once 'worker' is stripped")
    return problems


def main() -> int:
    text = build_exposition()
    problems = check(text)
    if "siddhi_tpu_slo_" not in text:
        problems.append(
            "lint deployment rendered no siddhi_tpu_slo_* family — the "
            "SLO compliance surface is unwired or unregistered")
    if "siddhi_tpu_mesh_" not in text:
        problems.append(
            "lint deployment rendered no siddhi_tpu_mesh_* family — the "
            "mesh fabric surface is unwired or unregistered")
    if 'worker="fabric"' not in text:
        problems.append(
            "lint deployment rendered no worker=\"fabric\" merged series — "
            "the federated collector is unwired or produced nothing")
    for phase in ("device_step", "egress_fence", "egress_decode",
                  "key_lookup", "key_lookup_cpu"):
        if f'phase="{phase}"' not in text:
            problems.append(
                f"lint deployment's device query rendered no "
                f"phase=\"{phase}\" histogram — the step's dispatch / "
                f"fence / decode split is unwired")
    if not re.search(r'siddhi_tpu_phase_latency_seconds_bucket\{'
                     r'[^}]*worker="h\d+"', text):
        problems.append(
            "lint deployment rendered no per-worker federated "
            "phase-latency histogram — child trackers did not federate")
    for p in problems:
        print(p)
    if problems:
        print(f"\n{len(problems)} problem(s) found.")
        return 1
    n = sum(1 for ln in text.splitlines()
            if ln and not ln.startswith("#"))
    print(f"OK: {n} sample(s) clean.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
