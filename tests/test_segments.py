"""The measured segments of a served batch, kind by kind: which ``phase.*``
trackers each of the five served runtime kinds counts over a few batches,
how many events each counts, and which ``siddhi:`` spans it opens, how
often, on which thread and inside which parent.

CPU only, async (the driver thread), one batch in the ring at a time: the
feed flushes behind every batch, so nothing here depends on the host's
speed. Counts are events (the trackers are event-weighted).
"""

import contextlib
import threading

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.observability import profiler
from test_step_runtime import KINDS, NFA_APP, _feed

HOPPING_APP = """
define stream S (k long, v long);
{device}
from S#window.hopping(200, 40)
select k, count() as c, sum(v) as t group by k order by c desc, t limit 2
insert into O;
"""


def _hopping_events(n=160, seed=3):
    """A tick apart: a batch of 16 spans 16 ms, a boundary every 40."""
    rng = np.random.default_rng(seed)
    return [("S", [int(rng.integers(5)), int(rng.integers(100))], 1000 + i)
            for i in range(n)]


def _burst_events(n=160):
    """38 rises then a fall, over and over: the fall closes every rise
    still waiting, more rows than a batch of 32 holds packed."""
    return [("S", [f"k{i % 3}", 95.0 if i % 40 < 38 else 5.0, i], 1000 + i)
            for i in range(n)]


# kind -> (app, events, batch, @device options)
CASES = {
    "stream": KINDS["stream"][:4],
    "hopping": (HOPPING_APP, _hopping_events(), 16, ", window='256'"),
    "nfa": (NFA_APP, _burst_events(), 32, ", slots='64'"),
    "partition": KINDS["partition"][:4],
    "keyed": KINDS["keyed"][:4],
}

# every kind's serial trackers and thread clocks, events counted: n =
# every event, c = the events of the batches a client's cycle spans (all
# but the first), p = the events of the batches that delivered rows
COMMON = {"ingress_queue": "n", "fill_wait": "n", "pack": "n",
          "device_step": "n", "egress_fence": "n", "egress_decode": "n",
          "lock_wait": "p", "sink_publish": "p", "publish_build": "p",
          "device_step_cpu": "n", "egress_fence_cpu": "n",
          "egress_decode_cpu": "n", "sink_publish_cpu": "p",
          "client_cycle": "c", "client_cpu": "c", "driver_cpu": "n"}
# kind -> (n, c, p, the kind's own trackers and their counts)
COUNTS = {
    "stream": (100, 84, 100, {}),
    "hopping": (160, 144, 48, {"hop_drain": 160, "hop_flush": 48}),
    "nfa": (160, 128, 128, {"decode_full": 128}),
    "partition": (200, 136, 200, {"route": 200, "route_cpu": 200}),
    "keyed": (200, 168, 200, {"key_lookup": 200, "key_lookup_cpu": 200}),
}

# span call -> (thread, parent call or None)
SPANS = {"seal.pack": ("client", None),
         "seal.key_lookup": ("client", "seal.pack"),
         "dispatch": ("driver", None),
         "dispatch.route": ("driver", "dispatch"),
         "collect": ("driver", None),
         "collect.fence": ("driver", "collect"),
         "collect.decode": ("driver", "collect"),
         "collect.decode.full": ("driver", "collect.decode"),
         "collect.decode.hop_flush": ("driver", "collect.decode"),
         "collect.decode.hop_drain": ("driver", "collect.decode"),
         "deliver": ("driver", None),
         "deliver.lock": ("driver", "deliver"),
         "deliver.publish": ("driver", "deliver"),
         "deliver.publish.build": ("driver", "deliver.publish")}
# every kind's spans a batch: b = every batch, d = a batch that delivered
# rows (the receiver of the StreamCallback builds its Event list too)
COMMON_SPANS = {"seal.pack": "b", "dispatch": "b", "collect": "b",
                "collect.fence": "b", "collect.decode": "b", "deliver": "d",
                "deliver.lock": "d", "deliver.publish": "d",
                "deliver.publish.build": "d"}
# kind -> (b, d, the kind's own spans and how often each opens)
SPAN_COUNTS = {
    "stream": (7, 7, {}),
    # the drain's span only round a serial batch's drain: the first
    "hopping": (10, 3, {"collect.decode.hop_flush": 3,
                        "collect.decode.hop_drain": 1}),
    "nfa": (5, 4, {"collect.decode.full": 4}),
    "partition": (4, 4, {"dispatch.route": 4}),
    "keyed": (7, 7, {"seal.key_lookup": 7}),
}


class _Annotations:
    """Stands in for ``jax.profiler``: each ``TraceAnnotation`` logs its
    open and close with the thread that made them."""

    def __init__(self):
        self.log = []

    def TraceAnnotation(self, name):   # noqa: N802 — jax.profiler's name
        @contextlib.contextmanager
        def annotation():
            self.log.append(("open", name, threading.get_ident()))
            yield
            self.log.append(("close", name, threading.get_ident()))
        return annotation()


def _served(kind):
    """The kind's events served a batch at a time: the events each of its
    probe's trackers counted, where any."""
    app, events, batch, options = CASES[kind]
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app.format(
            device=f"@device(strict='true', batch='{batch}'{options}, "
                   f"async='true')"), playback=True)
        rt.add_callback("O", StreamCallback(lambda evs: None))
        rt.start()
        for i in range(0, len(events), batch):
            _feed(rt, events[i:i + batch])
            rt.flush_device()
        bridge, = rt.device_bridges
        counts = {name: t.count for name, t in
                  bridge.probe.phases.trackers.items() if t.count}
    finally:
        m.shutdown()
    return counts


@pytest.fixture
def annotations(monkeypatch):
    fake = _Annotations()
    monkeypatch.setattr(profiler, "_jax_profiler", lambda: fake)
    return fake


def _spans(log, client):
    """(call, thread, enclosing call) of each opened span; the call is the
    span's name less ``siddhi:`` and its ``:<query>`` or ``:<stream>``."""
    out, open_on = [], {}
    for what, name, thread in log:
        call = name.split(":")[1]
        stack = open_on.setdefault(thread, [])
        if what == "open":
            role = "client" if thread == client else "driver"
            out.append((call, role, stack[-1] if stack else None))
            stack.append(call)
        else:
            assert stack.pop() == call, (name, stack)
    return out


@pytest.mark.parametrize("kind", list(CASES))
def test_a_served_kind_counts_its_trackers_and_opens_its_spans(annotations,
                                                               kind):
    counts = _served(kind)
    n, c, p, own = COUNTS[kind]
    want = {name: {"n": n, "c": c, "p": p}[k] for name, k in COMMON.items()}
    assert counts == {**want, **own}
    opened = _spans(annotations.log, threading.get_ident())
    b, d, own_spans = SPAN_COUNTS[kind]
    want_spans = {call: {"b": b, "d": d}[k]
                  for call, k in COMMON_SPANS.items()}
    seen = {}
    for call, role, around in opened:
        seen[call] = seen.get(call, 0) + 1
        thread, parent = SPANS[call]
        assert (role, around) == (thread, parent), (call, role, around)
    assert seen == {**want_spans, **own_spans}


def test_the_views_of_the_table_name_what_they_always_named():
    from siddhi_tpu.observability.phases import (
        CPU_OF, NESTED, PHASES, SEGMENTS, THREAD_CLOCKS)
    assert PHASES == (
        "ingress_parse", "ingress_queue", "ring_wait", "fill_wait", "pack",
        "device_step", "egress_fence", "egress_decode", "host_exec",
        "lock_wait", "sink_publish", "dcn_transit", "procmesh_transit")
    assert NESTED == {"route": "device_step", "key_lookup": "pack",
                      "decode_full": "egress_decode",
                      "hop_drain": "egress_decode",
                      "hop_flush": "egress_decode",
                      "publish_build": "sink_publish"}
    assert CPU_OF == {"device_step": "device_step_cpu", "route": "route_cpu",
                      "key_lookup": "key_lookup_cpu",
                      "egress_fence": "egress_fence_cpu",
                      "egress_decode": "egress_decode_cpu",
                      "sink_publish": "sink_publish_cpu"}
    assert sorted(THREAD_CLOCKS) == sorted(
        list(CPU_OF.values()) + ["client_cycle", "client_cpu", "driver_cpu"])
    # the spans a served batch opens for its segments, and a span's parent
    # is its segment's parent's span
    spans = {p: s.span for p, s in SEGMENTS.items() if s.span}
    assert spans == {
        "ring_wait": "submit.ring_wait", "pack": "seal.pack",
        "device_step": "dispatch", "egress_fence": "collect.fence",
        "egress_decode": "collect.decode", "lock_wait": "deliver.lock",
        "sink_publish": "deliver.publish", "route": "dispatch.route",
        "key_lookup": "seal.key_lookup", "decode_full": "collect.decode.full",
        "hop_drain": "collect.decode.hop_drain",
        "hop_flush": "collect.decode.hop_flush",
        "publish_build": "deliver.publish.build"}
    for part, parent in NESTED.items():
        assert SPANS[spans[part]][1] == spans[parent]
    assert all(s.when for s in SEGMENTS.values())
