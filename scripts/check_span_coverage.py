#!/usr/bin/env python
"""Span-coverage lint: every engine hop stamps its span or handoff.

The X-Ray contract (ISSUE 10): a sampled trace must never silently skip a
hop — each asynchronous boundary either records a span or explicitly hands
the trace to the far side. A hop that drops the trace makes every
waterfall read as if the time vanished, which is exactly the blind spot
the attribution layer exists to remove. Modeled on
``check_guard_coverage.py``: structural source checks per hop plus one
end-to-end build that asserts a real trace crossed them.

Hops checked:

1. **@async enqueue/delivery** — the junction stamps the trace + handoff
   mark at enqueue; delivery closes the queue wait as an ``ingress-queue``
   span and re-activates the trace;
2. **device dispatch/collect** — the bridge registers pending traces at
   packing, the seal closes groups FIFO, the driver's egress observes
   every consumed batch (so groups can't desynchronize) with its segments
   told apart (fence / decode, lock wait / publish, ring wait), each also
   a span on the profiler's clock; a keyed window's key lookup runs and is
   spanned inside the seal, never in ``dispatch``;
3. **DCN forward/receive** — outgoing frames carry sampled TraceContexts;
   both receive paths parse and re-activate them with a ``dcn`` hop span;
4. **fleet group step** — staging registers the active trace per member;
   the shared step drains every member's pending with a ``fleet`` span;
5. **solo/scalar fallback** — a fallback step still closes its spans
   (probe ``outcome='fallback'``; fleet solo tier ``outcome='solo'``).

Run from tier-1 (tests/test_xray.py); exits non-zero on any gap.
"""

import inspect
import os
import sys

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


def main() -> int:
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.device_bridge import (
        AsyncDeviceDriver,
        DeviceQueryBridge,
    )
    from siddhi_tpu.core.stream import InputHandler, StreamJunction
    from siddhi_tpu.fleet.group import FleetGroup
    from siddhi_tpu.tpu.step_runtime import StepRuntime
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

    # 2) device dispatch/collect
    check("device bridge registers pending traces at packing",
          "probe.pending" in src(DeviceQueryBridge.on_event))
    check("every flush seals its trace group at the emit",
          "_seal" in src(StepRuntime._maybe_flush)
          or "step_sealer" in src(StepRuntime._seal))
    check("driver egress observes every consumed batch (probe drains FIFO)",
          "observe" in src(AsyncDeviceDriver._collect_oldest)
          and "phases" in src(AsyncDeviceDriver._collect_oldest))
    from siddhi_tpu.observability.phases import PHASES
    check("phase vocabulary tells fence / decode, lock wait / publish and "
          "the ring wait apart",
          {"egress_fence", "egress_decode", "lock_wait", "sink_publish",
           "ring_wait", "ingress_queue"} <= set(PHASES))
    check("driver egress measures the split and puts it on the profiler's "
          "clock",
          all(k in src(AsyncDeviceDriver._collect_oldest) for k in (
              "collect_s", "lock_s", "ring_s", "deliver.lock",
              "deliver.publish"))
          and "decode_s" in src(StepRuntime.step_phases)
          and "ring_wait" in src(AsyncDeviceDriver.submit)
          and "collect.fence" in src(StepRuntime._fence)
          and "seal.pack" in src(StepRuntime._emit_batch))
    from siddhi_tpu.tpu.keyed_window import KeyedWindowRuntime
    check("a keyed window looks its keys up inside the seal, on the "
          "sealing thread, and its dispatch only launches the step",
          "_sealing" in src(StepRuntime._emit_batch)
          and "seal.key_lookup" in src(KeyedWindowRuntime._sealing)
          and "thread_time" in src(KeyedWindowRuntime._sealing)
          and "slots_of" not in src(KeyedWindowRuntime.dispatch))
    check("sync path measures the same split",
          "collect_s" in src(StepRuntime._timed_process)
          and "step_phases" in src(StepRuntime._timed_process))
    check("every segment a thread works in reads the thread's CPU clock "
          "beside the wall clock",
          all("thread_time" in src(f) for f in (
              AsyncDeviceDriver._dispatch, AsyncDeviceDriver._collect_oldest,
              StepRuntime._fence, StepRuntime._timed_process,
              StepRuntime._emit_batch)))
    check("probe closes fill-wait + device spans per batch",
          "fill-wait" in src(DeviceStepProbe.on_step)
          and "add_span" in src(DeviceStepProbe.on_step))

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
