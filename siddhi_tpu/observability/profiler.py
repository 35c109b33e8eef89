"""Device-path profiling: the program's own spans on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: a host span that lands
in whatever profiler session is running (``@app:profile(dir=...)`` below,
``jax.profiler.start_trace`` of an embedding program, a benchmark's traced
run), on the same clock as the device's operations. Outside a session it is
a flag test, so the spans are always armed: no annotation, option or
environment variable turns them on. They are per micro-batch, never per
event. Names are ``siddhi:<call>[.<part>]:<query>``: each measured segment's
span is named in ``phases.SEGMENTS`` beside its tracker (``seal.pack``,
``seal.key_lookup`` and ``submit.ring_wait`` on the client's thread, the rest
on the driver's), and the driver opens two wholes round them,
``siddhi:collect:<q>`` (fence and decode) and ``siddhi:deliver:<q>`` (lock
wait and publish). A ``StreamCallback``'s receiver names its build span by
its ``<stream>``. On the synchronous path the driver's spans open on the
client thread, and there is no ring, no lock wait and no ``deliver`` span.
The same boundaries feed the ``phase.*`` trackers, so an untraced run sees
them too; beside the wall clock the trackers read the thread's own CPU clock
at the same places (``phases.THREAD_CLOCKS``), which a span cannot carry.
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
