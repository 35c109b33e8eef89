#!/usr/bin/env python
"""Bench regression guard: a fresh ``bench.py`` HOST run vs BASELINE.json.

CI-runnable (invoked from tests/test_host_batch.py when ``BENCH_GUARD`` is
set): runs the bench host child (both host tiers — scalar interpreter and
the columnar micro-batch engine) on a reduced corpus and fails when

1. the columnar engine did not actually engage (``host_engine`` !=
   ``columnar`` — a silent fall-back to the interpreter is the regression
   this guard exists to catch);
2. host-side oracle parity broke (columnar vs scalar match counts);
3. the columnar/scalar speedup dropped below the tolerance band around
   BASELINE.json's ``host_baseline.columnar_vs_scalar_min`` (the ratio is
   same-machine, so it is robust to container speed differences — absolute
   ev/s numbers are NOT comparable across machines and are only reported).

An ``edge`` guard (``run_edge_guard``) pins the zero-object edge line of
the newest BENCH_r*.json against ``edge_baseline`` (rows/s floor,
objects-per-row == 0, worker parity + speedup floor).

An ``slo`` guard (``run_slo_guard``) runs a fresh ``bench.py --slo-child``
noisy-neighbour storm (reduced feed) and pins the autopilot's contract
vs BASELINE.json ``slo_baseline``: premium p99 within the declared budget
(ceiling scaled by 1/tol), ZERO premium sheds, best-effort absorbing the
shedding, and at least one controller decision taken.

A ``mesh`` guard (``run_mesh_guard``) runs a fresh ``bench.py
--mesh-child`` (reduced tenant population over the 8-device forced-host
mesh) and pins the fabric's contract vs BASELINE.json ``mesh_baseline``:
shape-locality placement measurably better than random (compiled programs
per host, lanes per step), the live migration and the host join/leave
elasticity cycle exactly-once vs solo oracles, and the cross-host scaling
efficiency above its (plumbing) floor.

A ``procmesh`` guard (``run_procmesh_guard``) runs a fresh ``bench.py
--procmesh-child`` (reduced feed over REAL host processes) and pins the
process fabric's contract vs BASELINE.json ``procmesh_baseline``: the
real-SIGKILL restart cycle exactly-once with zero dup chunks and at least
one actual respawn, kill→respawn→spill-drained recovery under the stored
ceiling, and the (core-limited) per-host-process scaling efficiency above
its floor.

A ``gray`` guard (``run_gray_guard``) runs a fresh ``bench.py
--gray-child`` (reduced feed, 2 host processes, one wedged mid-feed) and
pins the gray-failure ladder vs BASELINE.json ``gray_baseline``: the
heartbeat-green op-stalling worker classified WEDGED within the stored
detection ceiling and actually healed (respawn + tenant recovery), the
spill replay exactly-once (zero dups, victim AND innocent byte-identical
to solo oracles), and the hedged second attempt winning a
deterministically partitioned reply on a hedge-safe op.

A ``device_latency`` guard (``run_device_latency_guard``) additionally pins
the double-buffered pipeline's recorded evidence: when a bench report with a
``latency_mode`` line exists, its p99 must stay under
``device_baseline.p99_ceiling_ms`` and the pack/step overlap above
``device_baseline.overlap_efficiency_min``; phase-partial and host-only
reports are tolerated with a note instead of a crash.

Exit code 0 = ok, 1 = regression, 2 = could not measure.

Env knobs: ``BENCH_GUARD_EVENTS`` (default 60000), ``BENCH_GUARD_TOL``
(default 0.5 — the fraction of the stored speedup floor that must still
hold; 0.5 × 3.0 → the columnar engine must stay ≥1.5x the interpreter).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_guard(events: int, tol: float, deadline_s: int = 600) -> int:
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("host_baseline") or {}
    ratio_min = float(baseline.get("columnar_vs_scalar_min", 3.0))
    floor = tol * ratio_min

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_BASELINE_EVENTS": str(min(events, 20000)),
        "BENCH_ORACLE_EVENTS": str(events),
    }
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--host-child"],
            capture_output=True, text=True, timeout=deadline_s, env=env,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"GUARD: host bench exceeded {deadline_s}s", file=sys.stderr)
        return 2
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-6:]
        print("GUARD: host bench failed: " + " | ".join(tail),
              file=sys.stderr)
        return 2
    data = None
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            data = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if data is None:
        print("GUARD: no JSON in bench output", file=sys.stderr)
        return 2

    scalar = data.get("rate")
    columnar = data.get("host_batch_rate")
    engine = data.get("host_engine")
    failures = []
    if engine != "columnar":
        failures.append(
            f"columnar engine did not engage (host_engine={engine!r}, "
            f"error={data.get('host_batch_error')!r})")
    if data.get("host_batch_oracle_matches") != data.get("oracle_matches"):
        failures.append(
            f"host oracle parity broke: columnar="
            f"{data.get('host_batch_oracle_matches')} scalar="
            f"{data.get('oracle_matches')} over {events} events")
    ratio = None
    if scalar and columnar:
        ratio = columnar / scalar
        if ratio < floor:
            failures.append(
                f"columnar/scalar speedup {ratio:.2f}x below the tolerance "
                f"band (floor {floor:.2f}x = {tol} x stored "
                f"{ratio_min:.2f}x)")
    elif not failures:
        failures.append("missing host rates in bench output")

    print(json.dumps({
        "scalar_evps": round(scalar) if scalar else None,
        "columnar_evps": round(columnar) if columnar else None,
        "speedup": round(ratio, 2) if ratio else None,
        "floor": floor,
        "stored_seed_evps": baseline.get("scalar_evps"),
        "host_engine": engine,
        "parity_ok": data.get("host_batch_oracle_matches")
        == data.get("oracle_matches"),
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION: {f_}", file=sys.stderr)
    return 1 if failures else 0


def run_fleet_guard(tol: float, deadline_s: int = 600) -> int:
    """Multi-tenant fleet line vs BASELINE.json ``fleet_baseline``: a fresh
    ``bench.py --fleet-child`` run (reduced feed) must keep

    1. the fleet engaged (every tenant on a fleet bridge) with ONE compile
       per shape (shared-compilation dedupe across K tenants);
    2. per-tenant oracle parity (fleet == solo == scalar match counts);
    3. fleet/solo aggregate throughput above the tolerance band around the
       stored ``fleet_vs_solo_min`` (same-machine ratio — robust to
       container speed).
    """
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("fleet_baseline") or {}
    ratio_min = float(baseline.get("fleet_vs_solo_min", 3.0))
    floor = tol * ratio_min

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_TENANT_FEED": os.environ.get("BENCH_GUARD_TENANT_FEED",
                                            "6000"),
        "BENCH_FLEET_PATTERN_FEED": "0",    # ratio line only: keep CI fast
    }
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--fleet-child"],
            capture_output=True, text=True, timeout=deadline_s, env=env,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"GUARD: fleet bench exceeded {deadline_s}s", file=sys.stderr)
        return 2
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-6:]
        print("GUARD: fleet bench failed: " + " | ".join(tail),
              file=sys.stderr)
        return 2
    data = None
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            data = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if data is None:
        print("GUARD: no JSON in fleet bench output", file=sys.stderr)
        return 2

    failures = []
    tenants = data.get("tenants")
    if data.get("fleet_engaged") != tenants:
        failures.append(
            f"fleet did not engage every tenant "
            f"(engaged={data.get('fleet_engaged')} of {tenants})")
    if data.get("fleet_compiles") != 1:
        failures.append(
            f"shared compilation broke: {data.get('fleet_compiles')} "
            f"compiles for {tenants} homogeneous tenants (expected 1)")
    if not data.get("oracle_ok"):
        failures.append("per-tenant oracle parity broke "
                        "(fleet/solo/scalar match counts diverged)")
    ratio = data.get("fleet_vs_solo")
    if not ratio:
        failures.append("missing fleet_vs_solo in bench output")
    elif ratio < floor:
        failures.append(
            f"fleet/solo speedup {ratio:.2f}x below the tolerance band "
            f"(floor {floor:.2f}x = {tol} x stored {ratio_min:.2f}x)")
    # FleetGuard fault line (PR 8): when the bench ran the containment
    # scenario, the innocent tenants must keep their exact outputs and
    # their throughput must not collapse (loose wall-clock floor — the
    # 10% evidence bar lives in the BENCH json; the correctness soak is
    # tests/test_fleet_guard.py)
    if "fault_innocent_ratio" in data:
        if not data.get("fault_innocents_oracle_ok"):
            failures.append("innocent tenants' outputs diverged under a "
                            "contained tenant fault")
        fr = data.get("fault_innocent_ratio") or 0.0
        if fr < tol:
            failures.append(
                f"innocent-tenant throughput collapsed to {fr:.2f}x the "
                f"no-fault run during containment (floor {tol})")

    print(json.dumps({
        "tenants": tenants,
        "fleet_evps": data.get("fleet_evps"),
        "solo_evps": data.get("solo_evps"),
        "fleet_vs_solo": round(ratio, 2) if ratio else None,
        "floor": floor,
        "fleet_compiles": data.get("fleet_compiles"),
        "solo_compiles": data.get("solo_compiles"),
        "oracle_ok": data.get("oracle_ok"),
        "fault_innocent_ratio": data.get("fault_innocent_ratio"),
        "fault_ejections": data.get("fault_ejections"),
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION (fleet): {f_}", file=sys.stderr)
    return 1 if failures else 0


def run_slo_guard(tol: float, deadline_s: int = 420) -> int:
    """SLO-autopilot storm vs BASELINE.json ``slo_baseline``: a fresh
    ``bench.py --slo-child`` (16 tenants, one 10×-burst best-effort noisy
    neighbour) must keep

    1. the closed loop ENGAGED (≥1 controller decision on the flight
       trail — a storm that provokes no decision means the controller is
       unwired, the real regression this guard exists to catch);
    2. premium sheds at ZERO (best-effort absorbs, binary — no band);
    3. best-effort shedding actually absorbing the burst (> 0 rows);
    4. the converged premium p99 under the stored ceiling scaled by
       1/tol (wall-clock on a shared container, hence the slack —
       ``premium_p99_ms`` is the quiet window at the final operating
       point, re-measured after any mid-run stall the controller fixed).
    """
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("slo_baseline") or {}
    if not baseline:
        print(json.dumps({"slo_guard": "skipped",
                          "reason": "no slo_baseline in BASELINE.json"}))
        return 0
    ceiling = float(baseline.get("premium_p99_ceiling_ms", 100.0)) \
        / max(tol, 1e-9)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_SLO_FEED": os.environ.get("BENCH_GUARD_SLO_FEED", "12000"),
    }
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--slo-child"],
            capture_output=True, text=True, timeout=deadline_s, env=env,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"GUARD: slo bench exceeded {deadline_s}s", file=sys.stderr)
        return 2
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-6:]
        print("GUARD: slo bench failed: " + " | ".join(tail),
              file=sys.stderr)
        return 2
    data = None
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            data = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if data is None:
        print("GUARD: no JSON in slo bench output", file=sys.stderr)
        return 2

    failures = []
    if not data.get("decisions"):
        failures.append("controller took zero decisions under a "
                        f"{data.get('burst_factor')}x noisy-neighbour "
                        "storm (autopilot unwired?)")
    if data.get("premium_sheds", 1) != 0:
        failures.append(
            f"{data.get('premium_sheds')} premium rows shed — premium "
            f"lanes must never absorb a best-effort burst")
    if not data.get("besteffort_sheds"):
        failures.append("best-effort shed nothing — the burst was "
                        "absorbed by the shared window instead")
    p99 = data.get("premium_p99_ms")
    if p99 is None:
        failures.append("missing premium_p99_ms in slo bench output")
    elif p99 > ceiling:
        failures.append(
            f"converged premium p99 {p99:.1f}ms above the ceiling "
            f"{ceiling:.1f}ms "
            f"({baseline.get('premium_p99_ceiling_ms')}ms / {tol})")

    print(json.dumps({
        "tenants": data.get("tenants"),
        "burst_factor": data.get("burst_factor"),
        "premium_p99_ms": p99,
        "p99_ceiling_ms": ceiling,
        "budget_ms": data.get("budget_ms"),
        "decisions": data.get("decisions"),
        "decision_kinds": data.get("decision_kinds"),
        "premium_sheds": data.get("premium_sheds"),
        "besteffort_sheds": data.get("besteffort_sheds"),
        "window": [data.get("window_initial"), data.get("window_final")],
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION (slo): {f_}", file=sys.stderr)
    return 1 if failures else 0


def run_mesh_guard(tol: float, deadline_s: int = 600) -> int:
    """Mesh-fabric line vs BASELINE.json ``mesh_baseline``: a fresh
    ``bench.py --mesh-child`` (reduced tenant population) must keep

    1. shape-locality placement measurably better than random — the
       random/locality compiled-programs-per-host ratio above the stored
       floor scaled by ``tol``, and locality's lanes-per-step strictly
       above random's (the whole point of the placement layer);
    2. the live migration exactly-once (per-tenant solo-oracle
       byte-identical — binary, no band);
    3. the elasticity cycle ENGAGED (host join and leave each bulk-moved
       at least one tenant) and exactly-once;
    4. cross-host scaling efficiency at the largest mesh size above the
       stored floor scaled by ``tol`` (an in-process-mesh plumbing bound
       on this container — see the report's scaling_note; hardware curves
       come from the DCN tier)."""
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("mesh_baseline") or {}
    if not baseline:
        print(json.dumps({"mesh_guard": "skipped",
                          "reason": "no mesh_baseline in BASELINE.json"}))
        return 0
    adv_floor = tol * float(
        baseline.get("placement_compile_advantage_min", 4.0))
    eff_floor = tol * float(baseline.get("scaling_efficiency_min", 0.08))

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_MESH_PLACE_TENANTS":
            os.environ.get("BENCH_GUARD_MESH_TENANTS", "128"),
        "BENCH_MESH_FEED": os.environ.get("BENCH_GUARD_MESH_FEED", "1200"),
        "BENCH_MESH_PLACE_FEED": "96",
    }
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--mesh-child"],
            capture_output=True, text=True, timeout=deadline_s, env=env,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"GUARD: mesh bench exceeded {deadline_s}s", file=sys.stderr)
        return 2
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-6:]
        print("GUARD: mesh bench failed: " + " | ".join(tail),
              file=sys.stderr)
        return 2
    data = None
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            data = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if data is None:
        print("GUARD: no JSON in mesh bench output", file=sys.stderr)
        return 2

    failures = []
    place = data.get("placement") or {}
    adv = place.get("compile_advantage") or 0.0
    if adv < adv_floor:
        failures.append(
            f"shape-locality compile advantage {adv:.2f}x below the floor "
            f"{adv_floor:.2f}x ({tol} x stored "
            f"{baseline.get('placement_compile_advantage_min')})")
    if not (place.get("lanes_per_step_mean_locality", 0)
            > place.get("lanes_per_step_mean_random", 0)):
        failures.append(
            "locality placement did not widen lane packing "
            f"(lanes/step locality="
            f"{place.get('lanes_per_step_mean_locality')} vs random="
            f"{place.get('lanes_per_step_mean_random')})")
    mig = data.get("migration") or {}
    if not mig.get("oracle_ok"):
        failures.append("live migration broke exactly-once (moved tenant "
                        "or neighbours diverged from solo oracles)")
    ela = data.get("elasticity") or {}
    if not ela.get("oracle_ok"):
        failures.append("elasticity cycle broke exactly-once")
    if not ela.get("join_moves") or not ela.get("leave_moves"):
        failures.append(
            f"elasticity did not engage (join_moves="
            f"{ela.get('join_moves')} leave_moves="
            f"{ela.get('leave_moves')}) — plan recompute/bulk adoption "
            f"unwired?")
    eff = data.get("scaling_efficiency_max_size")
    if eff is None:
        failures.append("missing scaling_efficiency_max_size")
    elif eff < eff_floor:
        failures.append(
            f"mesh scaling efficiency {eff:.3f} below the floor "
            f"{eff_floor:.3f} ({tol} x stored "
            f"{baseline.get('scaling_efficiency_min')})")

    print(json.dumps({
        "tenants": place.get("tenants"),
        "hosts": data.get("hosts"),
        "compile_advantage": adv,
        "advantage_floor": adv_floor,
        "lanes_per_step": [place.get("lanes_per_step_mean_locality"),
                           place.get("lanes_per_step_mean_random")],
        "migration_oracle_ok": mig.get("oracle_ok"),
        "elasticity": [ela.get("join_moves"), ela.get("leave_moves"),
                       ela.get("oracle_ok")],
        "scaling_efficiency": eff,
        "efficiency_floor": eff_floor,
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION (mesh): {f_}", file=sys.stderr)
    return 1 if failures else 0


def run_procmesh_guard(tol: float, deadline_s: int = 600) -> int:
    """Process-fabric line vs BASELINE.json ``procmesh_baseline``: a fresh
    ``bench.py --procmesh-child`` (reduced feed, 2 then 4 host PROCESSES)
    must keep

    1. the real-SIGKILL restart cycle exactly-once (solo-oracle
       byte-identical, zero dup chunks — binary, no band) with at least
       one actual respawn;
    2. kill → respawn → spill-drained recovery under the stored ceiling
       scaled by 1/tol (parent clock);
    3. per-host-process scaling efficiency at the largest size above the
       stored floor scaled by ``tol`` — a CORE-LIMITED plumbing floor
       (see the baseline note: the recording container has one core, so
       this pins control-socket overhead, not hardware scaling);
    4. the parent-SIGKILL cycle (ISSUE 17): a durable fabric killed at a
       journal boundary and restarted must re-adopt/restore every worker
       and keep its sinks byte-exact vs solo oracles (binary, no band);
    5. the federated latency breakdown (ISSUE 18): every live worker
       reports per-phase histograms including the ``procmesh_transit``
       hop, the fabric-level merge is present with non-zero counts and
       p50 <= p99 per phase, and at least one sampled journey stitched
       parent dispatch + child transit onto ONE trace id (binary —
       structure and sanity, not latency bands: the recording box's
       absolute numbers are core-limited plumbing)."""
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("procmesh_baseline") or {}
    if not baseline:
        print(json.dumps({
            "procmesh_guard": "skipped",
            "reason": "no procmesh_baseline in BASELINE.json"}))
        return 0
    rec_ceiling = float(baseline.get("restart_recover_ceiling_s", 15.0)) \
        / max(tol, 1e-9)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_MESH_HOSTS":
            os.environ.get("BENCH_GUARD_PROCMESH_HOSTS", "4"),
        "BENCH_MESH_FEED":
            os.environ.get("BENCH_GUARD_PROCMESH_FEED", "1200"),
    }
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--procmesh-child"],
            capture_output=True, text=True, timeout=deadline_s, env=env,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"GUARD: procmesh bench exceeded {deadline_s}s",
              file=sys.stderr)
        return 2
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-6:]
        print("GUARD: procmesh bench failed: " + " | ".join(tail),
              file=sys.stderr)
        return 2
    data = None
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            data = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if data is None:
        print("GUARD: no JSON in procmesh bench output", file=sys.stderr)
        return 2

    failures = []
    rec = data.get("restart_recovery") or {}
    if not rec.get("oracle_ok"):
        failures.append(
            "real-SIGKILL restart broke exactly-once (killed tenant or "
            "neighbour diverged from its solo oracle)")
    if rec.get("dup_chunks"):
        failures.append(
            f"spill replay duplicated {rec.get('dup_chunks')} chunk(s) "
            f"through the child-side seq dedup")
    if not rec.get("restarts"):
        failures.append("no worker respawn happened — the SIGKILL was "
                        "never detected (supervisor monitor unwired?)")
    recover_s = rec.get("recover_s")
    if recover_s is None:
        failures.append("fleet never returned to all-alive with a drained "
                        "spill (recover_s missing)")
    elif recover_s > rec_ceiling:
        failures.append(
            f"restart recovery took {recover_s:.1f}s, over the ceiling "
            f"{rec_ceiling:.1f}s (stored "
            f"{baseline.get('restart_recover_ceiling_s')}s / {tol})")
    # ISSUE 17: the child also SIGKILLs the PARENT at a journal boundary
    # and restarts it — the durable fabric must re-adopt/restore every
    # worker and keep the sinks byte-exact (binary verdict, no band)
    prec = data.get("parent_recovery") or {}
    if not prec:
        failures.append("no parent_recovery block in the procmesh line "
                        "(durable-fabric phase did not run)")
    elif not prec.get("ok"):
        failures.append(
            "parent-SIGKILL recovery broke: "
            + (prec.get("error")
               or f"oracle_ok={prec.get('oracle_ok')} readopted="
                  f"{prec.get('readopted_workers')} restored="
                  f"{prec.get('restored_workers')} "
                  f"dup={prec.get('dup_chunks')}"))
    # scaling_efficiency_min is a FRACTION OF IDEAL, where ideal per-host
    # efficiency on this machine is min(hosts, cores)/hosts: on a 1-core
    # container (the recording box, see the baseline note) 8 worker
    # processes time-slice one core, so perfect plumbing still measures
    # 1/8 — judging the raw number against a fixed floor would make the
    # guard's verdict depend on where it runs, not on the code
    eff = data.get("scaling_efficiency_max_size")
    guard_hosts = max(1, int(data.get("hosts") or baseline.get("hosts", 1)))
    guard_cores = max(1, int(data.get("cores") or os.cpu_count() or 1))
    ideal_eff = min(guard_hosts, guard_cores) / guard_hosts
    eff_floor = tol * ideal_eff * \
        float(baseline.get("scaling_efficiency_min", 0.4))
    if eff is None:
        failures.append("missing scaling_efficiency_max_size")
    elif eff < eff_floor:
        failures.append(
            f"procmesh scaling efficiency {eff:.3f} below the floor "
            f"{eff_floor:.3f} ({tol} x stored fraction-of-ideal "
            f"{baseline.get('scaling_efficiency_min')} x ideal "
            f"{ideal_eff:.3f} at {guard_hosts} hosts/{guard_cores} "
            f"core(s)) — see procmesh_baseline note")
    # ISSUE 18: the federated observability pull — structural judgement
    # (every live worker federates, merge is sane, one trace id spans the
    # process hop), never latency bands
    fed = data.get("latency_breakdown") or {}
    fed_workers = fed.get("workers") or {}
    fed_merged = fed.get("merged") or {}
    if not fed:
        failures.append("no latency_breakdown block in the procmesh line "
                        "(federation phase did not run)")
    else:
        if not fed_workers:
            failures.append("federated scrape rendered zero live workers")
        for w, phases in fed_workers.items():
            if "procmesh_transit" not in phases:
                failures.append(
                    f"worker {w} federated without a procmesh_transit "
                    f"phase (ingest hop not instrumented)")
        if "procmesh_transit" not in fed_merged:
            failures.append("fabric-level merge lacks procmesh_transit")
        for ph, st in fed_merged.items():
            if not st.get("count"):
                failures.append(f"merged phase '{ph}' has zero samples")
            elif st.get("p50_ms", 0.0) > st.get("p99_ms", 0.0):
                failures.append(
                    f"merged phase '{ph}' p50 {st.get('p50_ms')}ms above "
                    f"p99 {st.get('p99_ms')}ms — merge broke monotonicity")
        if not fed.get("stitched_journeys"):
            failures.append(
                "no sampled journey carried ONE trace id across parent "
                "dispatch and child transit (stitching unwired)")

    print(json.dumps({
        "hosts": data.get("hosts"),
        "cores": data.get("cores"),
        "restarts": rec.get("restarts"),
        "recover_s": recover_s,
        "worker_downtime_s": rec.get("worker_downtime_s"),
        "replayed_chunks": rec.get("replayed_chunks"),
        "dup_chunks": rec.get("dup_chunks"),
        "restart_oracle_ok": rec.get("oracle_ok"),
        "parent_recover_s": prec.get("recover_s"),
        "parent_readopted_workers": prec.get("readopted_workers"),
        "parent_restored_tenants": prec.get("restored_tenants"),
        "parent_journal_replayed": prec.get("journal_records_replayed"),
        "parent_recovery_ok": prec.get("ok"),
        "scaling_efficiency": eff,
        "efficiency_floor": eff_floor,
        "efficiency_ideal": ideal_eff,
        "recover_ceiling_s": rec_ceiling,
        "federated_workers": sorted(fed_workers),
        "federated_phases": sorted(fed_merged),
        "stitched_journeys": fed.get("stitched_journeys"),
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION (procmesh): {f_}", file=sys.stderr)
    return 1 if failures else 0


def run_gray_guard(tol: float, deadline_s: int = 600) -> int:
    """Gray-failure line vs BASELINE.json ``gray_baseline`` (ISSUE 19): a
    fresh ``bench.py --gray-child`` (reduced feed, 2 host PROCESSES, one
    wedged mid-feed) must keep

    1. the wedged worker — alive, heartbeating, every substantive op
       stalling — DETECTED (``decision:worker_wedged`` on the flight
       ring) within the stored detection ceiling scaled by 1/tol, and
       actually healed (>= 1 respawn, tenant recovered);
    2. the spill replay exactly-once: zero dup chunks and BOTH tenants
       byte-identical to their solo oracles (binary, no band) — the
       innocent neighbour on the other host process included;
    3. the hedge path live: one deterministically partitioned reply on a
       hedge-safe op won by the fresh-connection second attempt
       (``hedge_wins`` >= stored floor — binary plumbing, not a latency
       band: the chaos partition raises immediately, so wall time says
       nothing)."""
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("gray_baseline") or {}
    if not baseline:
        print(json.dumps({
            "gray_guard": "skipped",
            "reason": "no gray_baseline in BASELINE.json"}))
        return 0
    det_ceiling = float(baseline.get("detection_ceiling_s", 5.0)) \
        / max(tol, 1e-9)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "BENCH_GRAY_FEED":
            os.environ.get("BENCH_GUARD_GRAY_FEED", "640"),
    }
    try:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--gray-child"],
            capture_output=True, text=True, timeout=deadline_s, env=env,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"GUARD: gray bench exceeded {deadline_s}s",
              file=sys.stderr)
        return 2
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()[-6:]
        print("GUARD: gray bench failed: " + " | ".join(tail),
              file=sys.stderr)
        return 2
    data = None
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            data = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if data is None:
        print("GUARD: no JSON in gray bench output", file=sys.stderr)
        return 2

    failures = []
    wedge = data.get("wedge") or {}
    detection_s = wedge.get("detection_s")
    if detection_s is None:
        failures.append(
            "wedged worker never classified — no decision:worker_wedged "
            "on the flight ring (latency-evidence ladder unwired?)")
    elif detection_s > det_ceiling:
        failures.append(
            f"wedge detection took {detection_s:.2f}s, over the ceiling "
            f"{det_ceiling:.2f}s (stored "
            f"{baseline.get('detection_ceiling_s')}s / {tol})")
    if not wedge.get("restarts"):
        failures.append(
            "wedged worker never respawned — classified but the "
            "down-ladder actuation (kill -> respawn) did not follow")
    if wedge.get("heal_s") is None:
        failures.append(
            "fleet never healed after the wedge (respawn + tenant "
            "recovery incomplete at the child's deadline)")
    if wedge.get("dup_chunks"):
        failures.append(
            f"wedge spill replay duplicated {wedge.get('dup_chunks')} "
            f"chunk(s) through the child-side seq dedup")
    if not wedge.get("oracle_ok"):
        failures.append(
            "wedge cycle broke exactly-once (victim or innocent tenant "
            "diverged from its solo oracle)")
    hedge = data.get("hedge") or {}
    wins_floor = int(baseline.get("hedge_wins_min", 1))
    if (hedge.get("hedge_wins") or 0) < wins_floor:
        failures.append(
            f"hedged retry won {hedge.get('hedge_wins')} time(s), below "
            f"the stored floor {wins_floor} — the partitioned-reply "
            f"second attempt is unwired or lost its budget")

    print(json.dumps({
        "hosts": data.get("hosts"),
        "detection_s": detection_s,
        "detection_ceiling_s": det_ceiling,
        "heal_s": wedge.get("heal_s"),
        "restarts": wedge.get("restarts"),
        "wedge_count": wedge.get("wedge_count"),
        "replayed_chunks": wedge.get("replayed_chunks"),
        "dup_chunks": wedge.get("dup_chunks"),
        "oracle_ok": wedge.get("oracle_ok"),
        "innocent_evps_during_wedge":
            wedge.get("innocent_evps_during_wedge"),
        "hedge_attempts": hedge.get("hedge_attempts"),
        "hedge_wins": hedge.get("hedge_wins"),
        "hedge_wins_floor": wins_floor,
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION (gray): {f_}", file=sys.stderr)
    return 1 if failures else 0


def _latest_device_report():
    """The report the device_latency guard judges: the file named by
    ``BENCH_GUARD_DEVICE_REPORT``, else the highest-numbered BENCH_r*.json
    in the repo root. Returns (path | None, parsed | None, note | None) —
    unreadable/partial files become notes, never exceptions."""
    import glob
    import re
    path = os.environ.get("BENCH_GUARD_DEVICE_REPORT")
    if not path:
        def _round(p):
            m = re.search(r"BENCH_r(\d+)\.json$", p)
            return int(m.group(1)) if m else -1
        cands = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")),
                       key=_round)
        if not cands:
            return None, None, "no BENCH_r*.json reports in the repo"
        path = cands[-1]
    try:
        with open(path) as f:
            return path, json.load(f), None
    except (OSError, json.JSONDecodeError) as e:
        return path, None, f"unreadable report {path}: {e}"


def run_device_latency_guard(tol: float) -> int:
    """Device latency/overlap guard vs BASELINE.json ``device_baseline``:
    when the newest bench report carries device evidence from the
    double-buffered pipeline (a ``latency_mode`` line), enforce

    1. p99 detection latency under the stored ceiling (scaled by 1/tol);
    2. pack/step overlap efficiency above the stored floor (scaled by tol).

    Reports WITHOUT that evidence — host-only fallbacks, phase-partial
    rounds where the latency or throughput phase died, pre-pipeline
    rounds — are tolerated: the guard prints what is missing (including
    per-phase statuses when present) and passes. A hung device phase
    already cost its phase; it must not also turn CI red."""
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("device_baseline") or {}
    if not baseline:
        print(json.dumps({"device_guard": "skipped",
                          "reason": "no device_baseline in BASELINE.json"}))
        return 0
    ceiling = float(baseline.get("p99_ceiling_ms", 250.0)) / max(tol, 1e-9)
    overlap_floor = tol * float(baseline.get("overlap_efficiency_min", 1.9))

    path, data, note = _latest_device_report()
    if data is None:
        print(json.dumps({"device_guard": "skipped", "reason": note}))
        return 0
    skip = {"device_guard": "skipped", "report": os.path.basename(path),
            "phases": data.get("device_phases")}
    platform = data.get("platform") or \
        (data.get("device_partial") or {}).get("platform")
    if platform == "cpu":
        # a CPU-container round is not device evidence: its latencies say
        # nothing about the pipeline the ceiling was recorded against
        skip["reason"] = "report platform is cpu (no accelerator round)"
        print(json.dumps(skip))
        return 0
    lm = data.get("latency_mode") or (data.get("device_partial")
                                      or {}).get("latency_mode")
    if lm is None:
        skip["reason"] = ("no latency_mode line (pre-pipeline report, "
                          "host-only fallback, or dead latency phase)")
        print(json.dumps(skip))
        return 0

    failures = []
    p99 = lm.get("p99_ms")
    if p99 is None:
        skip["reason"] = "latency_mode line lacks p99_ms"
        print(json.dumps(skip))
        return 0
    if p99 > ceiling:
        failures.append(
            f"latency-mode p99 {p99:.1f}ms above the ceiling "
            f"{ceiling:.1f}ms ({baseline.get('p99_ceiling_ms')}ms / {tol})")
    overlap = data.get("ingest_overlap_efficiency") or \
        (data.get("device_partial") or {}).get("overlap_efficiency")
    if overlap is None:
        # throughput phase died but latency survived: judge what exists
        print(f"GUARD NOTE (device): no overlap line in "
              f"{os.path.basename(path)} (throughput phase missing)",
              file=sys.stderr)
    elif overlap < overlap_floor:
        failures.append(
            f"overlap efficiency {overlap:.2f} below the floor "
            f"{overlap_floor:.2f} ({tol} x stored "
            f"{baseline.get('overlap_efficiency_min')})")

    print(json.dumps({
        "report": os.path.basename(path),
        "latency_mode_p99_ms": p99,
        "p99_ceiling_ms": ceiling,
        "overlap_efficiency": overlap,
        "overlap_floor": overlap_floor,
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION (device): {f_}", file=sys.stderr)
    return 1 if failures else 0


def run_edge_guard(tol: float) -> int:
    """Zero-object edge guard vs BASELINE.json ``edge_baseline``: when the
    newest bench report carries an ``edge`` line, enforce

    1. ZERO Event/StreamEvent constructions per row on the rows path (the
       zero-object invariant is binary — no tolerance band);
    2. rows/s above the stored floor scaled by ``tol`` (absolute, like the
       device p99 ceiling — same-machine across CI runs);
    3. worker-count parity intact, and the workers speedup above the
       stored floor (the STORED value reflects this container's measured
       thread ceiling, recorded alongside in the report — not the 2x
       aspiration, which needs ≥4 real cores).

    Reports without an edge line (device-focused runs, pre-PR 11 rounds)
    are tolerated with a note."""
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        baseline = json.load(f).get("edge_baseline") or {}
    if not baseline:
        print(json.dumps({"edge_guard": "skipped",
                          "reason": "no edge_baseline in BASELINE.json"}))
        return 0
    rows_floor = tol * float(baseline.get("rows_per_s_min", 1_000_000))
    speed_floor = tol * float(baseline.get("workers_speedup_min", 1.0))

    path, data, note = _latest_device_report()
    if data is None:
        print(json.dumps({"edge_guard": "skipped", "reason": note}))
        return 0
    edge = data.get("edge")
    if edge is None:
        print(json.dumps({"edge_guard": "skipped",
                          "report": os.path.basename(path),
                          "reason": "no edge line in the report"}))
        return 0

    failures = []
    if edge.get("objects_per_row", 1) != 0:
        failures.append(
            f"rows path leaked objects: {edge.get('objects_per_row')} "
            f"Event/StreamEvent constructions per row (expected 0)")
    rows = edge.get("rows_per_s") or 0
    if rows < rows_floor:
        failures.append(
            f"edge rows/s {rows:,} below the floor {rows_floor:,.0f} "
            f"({tol} x stored {baseline.get('rows_per_s_min'):,})")
    if not edge.get("workers_parity_ok", True):
        failures.append("parallel host tier parity broke: match counts "
                        "diverged across worker counts")
    speed = max(edge.get("workers_speedup_2") or 0.0,
                edge.get("workers_speedup_4") or 0.0)
    if speed < speed_floor:
        failures.append(
            f"parallel tier speedup {speed:.2f}x below the floor "
            f"{speed_floor:.2f}x ({tol} x stored "
            f"{baseline.get('workers_speedup_min')})")

    print(json.dumps({
        "report": os.path.basename(path),
        "rows_per_s": rows,
        "rows_floor": rows_floor,
        "objects_per_row": edge.get("objects_per_row"),
        "workers_speedup": speed,
        "speedup_floor": speed_floor,
        "workers_parity_ok": edge.get("workers_parity_ok"),
        "ingress": edge.get("ingress"),
        "ok": not failures,
    }))
    for f_ in failures:
        print(f"GUARD REGRESSION (edge): {f_}", file=sys.stderr)
    return 1 if failures else 0


def main() -> int:
    events = int(os.environ.get("BENCH_GUARD_EVENTS", 60000))
    tol = float(os.environ.get("BENCH_GUARD_TOL", 0.5))
    rc = run_guard(events, tol)
    drc = run_device_latency_guard(tol)
    erc = run_edge_guard(tol)
    if os.environ.get("BENCH_GUARD_SKIP_FLEET", "") == "1":
        return rc or drc or erc
    frc = run_fleet_guard(tol)
    src = run_slo_guard(tol)
    mrc = prc = grc = 0
    if os.environ.get("BENCH_GUARD_SKIP_MESH", "") != "1":
        mrc = run_mesh_guard(tol)
        prc = run_procmesh_guard(tol)
        grc = run_gray_guard(tol)
    return rc or frc or src or drc or erc or mrc or prc or grc


if __name__ == "__main__":
    sys.exit(main())
