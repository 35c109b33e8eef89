"""Device-path profiling: the program's own spans on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: a host span that lands
in whatever profiler session is running (``@app:profile(dir=...)`` below,
``jax.profiler.start_trace`` of an embedding program, a benchmark's traced
run), on the same clock as the device's operations. Outside a session it is
a flag test, so the spans are always armed: no annotation, option or
environment variable turns them on. They are per micro-batch, never per
event. Names are ``siddhi:<call>[.<part>]:<query>``:

===============================  ========  ================================
span                             thread    opens / closes
===============================  ========  ================================
``siddhi:seal.pack:<q>``         client    the builder's ``emit()`` in the
                                           runtime's ``flush`` (engine lock
                                           held)
``siddhi:seal.key_lookup:<q>``   client    inside it, a keyed window only:
                                           the batch's keys to slots in the
                                           key directory
``siddhi:submit.ring_wait:<q>``  client    the wait of
                                           ``AsyncDeviceDriver.submit``,
                                           only when the ring is full
``siddhi:dispatch:<q>``          driver    ``rt.dispatch(batch)``: copies in
                                           and the launch, one enqueue
``siddhi:collect:<q>``           driver    ``rt.collect(token)``, whole
``siddhi:collect.fence:<q>``     driver    inside the runtime's ``collect``:
                                           until the step's outputs are
                                           ready on the device and the first
                                           of them is on the host
``siddhi:collect.decode:<q>``    driver    the rest of ``collect``: the
                                           other copies out, the mask,
                                           string codes resolved
``siddhi:collect.decode.full``   driver    inside it, only for a batch of
``:<q>``                                   an NFA whose rows pass its packed
                                           table in some lane: the decode
                                           of its ``full`` table
``siddhi:collect.decode``        driver    inside it, a hopping window only:
``.hop_flush:<q>``                         the decode of a batch whose step
                                           fired a boundary with rows
``siddhi:collect.decode``        driver    inside it, a hopping window's
``.hop_drain:<q>``                         serial batch only (one whose step
                                           may have deferred a boundary):
                                           the read of ``hop_next`` out of
                                           the live state and any empty
                                           steps for deferred boundaries
``siddhi:deliver:<q>``           driver    from asking for the engine lock
                                           to ``rt.deliver`` returning
``siddhi:deliver.lock:<q>``      driver    asking for the engine lock until
                                           it is held
``siddhi:deliver.publish:<q>``   driver    ``rt.deliver(chunk)``: the chunk
                                           to the junction, callbacks
``siddhi:deliver.publish``       driver    inside it, what the engine builds
``.build:<q | stream>``                    for a subscriber that takes
                                           events: rows and ``StreamEvent``s
                                           in the bridge (``<q>``), or the
                                           ``Event`` list a
                                           ``StreamCallback``'s receiver
                                           builds from a columnar chunk
                                           (named by the ``<stream>``); the
                                           rest is the subscriber's function
===============================  ========  ================================

(On the synchronous path the driver's spans open on the client thread, and
there is no ring, no lock wait and no ``deliver`` span.) The same boundaries
feed the ``phase.*`` trackers (``phases.py``), so an untraced run sees them
too; beside the wall clock the trackers read the thread's own CPU clock at
the same places (``phases.THREAD_CLOCKS``), which a span cannot carry.
``@app:profile(dir='/tmp/jaxtrace')`` captures a full profiler trace
between ``start()`` and ``shutdown()``. Everything degrades to a no-op when
``jax.profiler`` is unavailable — profiling must never take an app down.
"""

from __future__ import annotations

import contextlib
import functools
import logging

log = logging.getLogger("siddhi_tpu.observability")


@functools.cache
def _jax_profiler():
    try:
        import jax.profiler as jp
        return jp
    except Exception:       # noqa: BLE001 — profiling is strictly optional
        return None


def span(name: str):
    """Context manager: a host span named ``name`` on the profiler's clock."""
    jp = _jax_profiler()
    if jp is None:
        return contextlib.nullcontext()
    return jp.TraceAnnotation(name)


class DeviceProfiler:
    """``@app:profile(dir=...)``: trace capture for one app's lifetime."""

    def __init__(self, trace_dir=None):
        self.trace_dir = trace_dir
        self._jp = _jax_profiler()
        self._tracing = False

    def start(self) -> None:
        if self.trace_dir is None or self._jp is None or self._tracing:
            return
        try:
            self._jp.start_trace(self.trace_dir)
            self._tracing = True
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            log.warning("@app:profile: start_trace failed: %s", e)

    def stop(self) -> None:
        if not self._tracing:
            return
        self._tracing = False
        try:
            self._jp.stop_trace()
        except Exception as e:  # noqa: BLE001
            log.warning("@app:profile: stop_trace failed: %s", e)


def parse_profile_annotation(ann) -> DeviceProfiler:
    return DeviceProfiler(trace_dir=ann.get("dir"))
