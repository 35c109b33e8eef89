#!/usr/bin/env python
"""Span-coverage lint: every engine hop stamps its span or handoff.

The X-Ray contract (ISSUE 10): a sampled trace must never silently skip a
hop — each asynchronous boundary either records a span or explicitly hands
the trace to the far side. A hop that drops the trace makes every
waterfall read as if the time vanished, which is exactly the blind spot
the attribution layer exists to remove. Modeled on
``check_guard_coverage.py``: structural source checks per hop, the device
hop's served deployments, and one end-to-end build that asserts a real
trace crossed them.

Hops checked:

1. **@async enqueue/delivery** — the junction stamps the trace + handoff
   mark at enqueue; delivery closes the queue wait as an ``ingress-queue``
   span and re-activates the trace;
2. **device dispatch/collect** — run, not read: one small served
   deployment of each kind (stream, hopping stream, NFA, partitioned NFA,
   keyed window) over a few batches, every span it opens and every tracker
   it counts held to ``observability.phases.SEGMENTS``: each segment's span
   opens on its thread and a part's inside its parent's (so a keyed
   window's key lookup opens inside the seal, never in ``dispatch``), every
   span of the table opens somewhere, and each tracker counts the events
   its entry says; a sampled trace crosses the device hop (below);
3. **DCN forward/receive** — outgoing frames carry sampled TraceContexts;
   both receive paths parse and re-activate them with a ``dcn`` hop span;
4. **fleet group step** — staging registers the active trace per member;
   the shared step drains every member's pending with a ``fleet`` span;
5. **solo/scalar fallback** — a fallback step still closes its spans
   (probe ``outcome='fallback'``; fleet solo tier ``outcome='solo'``).

Run from tier-1 (tests/test_xray.py); exits non-zero on any gap.
"""

import contextlib
import inspect
import os
import sys
import threading

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

failures = []


def check(name, cond, detail=""):
    if cond:
        print(f"OK   {name}")
    else:
        failures.append(name)
        print(f"FAIL {name} {detail}")


def src(obj) -> str:
    return inspect.getsource(obj)


SERVED = {
    # kind -> (query, batch, @device options, its parts: counted for every
    # event ("n") or for some)
    "stream": ("from S[v > 10]#window.length(8) select k, sum(v) as t", 16,
               "", {}),
    "hopping": ("from S#window.hopping(200, 40) select k, count() as c "
                "group by k order by c desc limit 1", 16, ", window='256'",
                {"hop_drain": "n", "hop_flush": "some"}),
    "nfa": ("from every a=S[v > 90] -> b=S[v < 10] within 40 "
            "select a.k as k, b.v as v", 32, ", slots='64'",
            {"decode_full": "some"}),
    "partition": ("from every a=S[v > 50] -> b=S[v > a.v] within 400 "
                  "select a.v as v1, b.v as v2", 64, ", slots='32', lanes='4'",
                  {"route": "n"}),
    "keyed": ("from S[v > 20]#window.length(3) select k, max(v) as hi", 32,
              ", keys='8'", {"key_lookup": "n"}),
}
# every kind's: the serial phases of every batch, and what every delivery
# counts; the spans that open on the thread that seals (or submits) a batch
EVERY = {"pack": "n", "device_step": "n", "egress_fence": "n",
         "egress_decode": "n", "publish_build": "sink_publish"}
CLIENT = {"ring_wait", "pack", "key_lookup"}


class _Annotations:
    """Stands in for ``jax.profiler``: each annotation logs (open | close,
    name, thread)."""
    log: list = []

    @classmethod
    @contextlib.contextmanager
    def TraceAnnotation(cls, name):     # noqa: N802 — jax.profiler's name
        cls.log.append(("open", name, threading.get_ident()))
        yield
        cls.log.append(("close", name, threading.get_ident()))


def _serve(kind, query, batch, options):
    """The kind served async, 160 events a batch at a time, once behind a
    full ring: the events each of its probe's trackers counted."""
    from siddhi_tpu import SiddhiManager, StreamCallback
    app = f"@device(strict='true', batch='{batch}'{options}, async='true')" \
        f"\n{query} insert into O;"
    if kind in ("partition", "keyed"):
        app = f"partition with (k of S) begin\n{app}\nend;"
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "define stream S (k long, v long);\n" + app, playback=True)
    rt.add_callback("O", StreamCallback(lambda evs: None))
    rt.start()
    driver = rt.device_bridges[0].driver
    held = range(batch, batch * (driver.depth + 2))
    for i in range(160):
        if i == held.start:         # a ring full behind a paused driver
            driver.pause()
        v = (95 if i % 40 < 38 else 5) if kind == "nfa" else i * 37 % 100
        rt.input_handler("S").send([i % 6, v], timestamp=1000 + i)
        if i == held.stop - 1:
            driver.resume()
        if i % batch == batch - 1 and i not in held:
            rt.flush_device()
    driver.resume()
    rt.flush_device()
    n = {p: t.count for p, t in
         rt.device_bridges[0].probe.phases.trackers.items()}
    m.shutdown()
    return n


def check_served_segments():
    from siddhi_tpu.observability import profiler
    from siddhi_tpu.observability.phases import CPU_OF, NESTED, SEGMENTS
    seg_of = {s.span: p for p, s in SEGMENTS.items() if s.span}
    opened, real = set(), profiler._jax_profiler
    profiler._jax_profiler = lambda: _Annotations
    for kind, (query, batch, options, own) in SERVED.items():
        _Annotations.log = []
        n = _serve(kind, query, batch, options)
        bad, stacks = [], {}
        for what, name, thread in _Annotations.log:
            stack = stacks.setdefault(thread, [])
            call = name.split(":")[1]
            p = seg_of.get(call)
            if what == "close":
                stack.pop()
            elif p is not None and (
                    (thread == threading.get_ident()) != (p in CLIENT)
                    or p in NESTED
                    and stack[-1:] != [SEGMENTS[NESTED[p]].span]):
                bad.append((call, stack[-1:]))
            if what == "open":
                opened.add(p)
                stack.append(call)
        check(f"{kind}: each segment's span opens on its thread, a part's "
              f"inside its parent's", not bad, f"{bad[:3]}")
        rule, wrong = {**EVERY, **own}, []
        for p in SEGMENTS:
            want = rule.get(p, "none" if p in NESTED else "some")
            ok = {"n": n[p] == 160, "none": n[p] == 0,
                  "some": n[p] <= n.get(NESTED.get(p), 160)}.get(
                want, n[p] == n.get(want))
            if not ok or n.get(CPU_OF.get(p), n[p]) != n[p]:
                wrong.append((p, n[p]))
        check(f"{kind}: each tracker counts the events its entry says",
              not wrong, f"{wrong}")
    profiler._jax_profiler = real
    spanned = {p for p, s in SEGMENTS.items() if s.span}
    check("every span of the table opens in a served deployment",
          spanned <= opened, f"(never: {sorted(spanned - opened)})")


def main() -> int:
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.stream import InputHandler, StreamJunction
    from siddhi_tpu.fleet.group import FleetGroup
    from siddhi_tpu.observability import DeviceStepProbe, phase_of_stage
    from siddhi_tpu.resilience.device_guard import DeviceGuard
    from siddhi_tpu.resilience.fleet_guard import FleetGuard
    from siddhi_tpu.tpu import dcn

    # 1) @async enqueue/delivery
    check("@async enqueue stamps trace + handoff mark",
          "mark_handoff" in src(StreamJunction.send_event)
          and "mark_handoff" in src(StreamJunction.send_events))
    check("@async delivery closes the queue span and re-activates",
          "close_handoff" in src(StreamJunction._activate_trace)
          and "_activate_trace" in src(StreamJunction.deliver_event)
          and "_activate_trace" in src(StreamJunction.deliver_events))
    check("ingress sampling covers send AND bulk send_rows",
          "maybe_trace" in src(InputHandler.send)
          and "maybe_trace" in src(InputHandler.send_rows))

    # 2) device dispatch/collect: served deployments, run
    check_served_segments()

    # 3) DCN forward/receive
    check("DCN ingest samples and forwards trace contexts",
          "maybe_trace" in src(dcn.DCNWorker.ingest)
          and "context_of" in src(dcn.DCNWorker.ingest))
    check("DCN frames carry the context block",
          "_pack_ctxs" in src(dcn.DCNWorker._forward))
    check("DCN receive paths re-activate contexts (dcn hop span)",
          "_unpack_ctxs" in src(dcn.DCNWorker._handle_rows)
          and "_adopt_ctxs" in src(dcn.DCNWorker._handle_rows)
          and "_unpack_ctxs" in src(dcn.DCNWorker._apply_frame_locally)
          and "_adopt_ctxs" in src(dcn.DCNWorker._apply_frame_locally))

    # 3b) procmesh ingest hop (ISSUE 18): the parent fabric stamps the
    # context onto the control-socket ingest op; the child adopts it ONLY
    # behind the seq dedup (lost-ack retries never double spans), records
    # the transit span + phase histogram, and ships the journey tail back
    from siddhi_tpu.mesh.fabric import MeshFabric
    from siddhi_tpu.procmesh.host import ProcMeshHost, RuntimeProxy
    from siddhi_tpu.procmesh.worker import WorkerServer
    check("fabric dispatch packs the sampled context onto the ingest op",
          "context_of" in src(MeshFabric._apply_locked)
          and "dispatch" in src(MeshFabric._apply_locked))
    check("proxy ships the context in the ingest header",
          "trace" in src(RuntimeProxy.send_chunk))
    check("child adopts ONLY on actual apply (behind the seq dedup)",
          "_apply_traced" in src(WorkerServer.op_ingest)
          and "t.applied" in src(WorkerServer.op_ingest))
    check("child stamps the procmesh transit span + phase histogram",
          "adopt" in src(WorkerServer._apply_traced)
          and "procmesh_transit" in src(WorkerServer._apply_traced)
          and "transit" in src(WorkerServer._apply_traced))
    check("child ships grown journeys; parent stitches with clock offset",
          "_trace_tail" in src(WorkerServer.op_flight)
          and "stitch" in src(ProcMeshHost.forward_flight)
          and "offset_ns" in src(ProcMeshHost.forward_flight))

    # 4) fleet group step
    check("fleet staging registers the active trace per member",
          all("_register_trace" in src(f) for f in (
              FleetGroup.stage_event, FleetGroup.stage_events,
              FleetGroup.stage_rows)))
    check("fleet shared step drains every member's pending",
          "_drain_all_traces" in src(FleetGroup._step))

    # 5) solo/scalar fallback
    check("device fallback steps still close spans (outcome=fallback)",
          "fallback" in src(DeviceStepProbe.on_step))
    check("device guard forwards the probe's phase hook on fallback",
          "device_path" in src(DeviceGuard.install))
    check("fleet solo tier drains pendings (outcome=solo/scalar)",
          "_drain_traces" in src(FleetGuard._after_solo_batch)
          and "_drain_traces" in src(FleetGuard.flush_solo))

    # every stage name used in the engine classifies into a known phase
    for stage in ("ingress", "queue", "query", "fill-wait", "device",
                  "fleet", "sink", "dcn", "procmesh"):
        check(f"stage '{stage}' classifies into an X-Ray phase",
              isinstance(phase_of_stage(stage), str))

    # end-to-end: a sampled trace actually crosses async + device hops
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app(name='lint-span')\n@app:trace(sample='1/1')\n"
            "@async(buffer.size='32')\n"
            "define stream S (v double);\n"
            "@device(batch='8') from S[v > 0.0] select v insert into Out;",
            playback=True)
        rt.start()
        ih = rt.input_handler("S")
        for i in range(16):
            ih.send([float(i + 1)], timestamp=1000 + i)
        rt.drain_async()
        rt.flush_device()
        stages = set()
        for tr in rt.observability.tracer.ring:
            stages |= tr.stages()
        check("end-to-end trace crossed ingress/queue/fill-wait/device",
              {"ingress", "queue", "fill-wait", "device"} <= stages,
              f"(saw {sorted(stages)})")
        spans = [s for tr in rt.observability.tracer.ring
                 for s in tr.spans]
        check("every span carries a waterfall start offset",
              all(s.start_offset_ns >= 0 for s in spans) and spans)
    finally:
        m.shutdown()

    if failures:
        print(f"\n{len(failures)} span-coverage gap(s)", file=sys.stderr)
        return 1
    print("\nspan coverage OK: async, device, DCN, fleet, fallback hops "
          "all stamp spans or handoffs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
