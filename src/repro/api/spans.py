"""Host spans of the driver: named phases on the profiler's timeline and
durations through ``jax.monitoring``.

``span("run.keys")`` opens a ``jax.profiler.TraceAnnotation`` named
``fedmm.run.keys``, so the phase lands on the profiler's host line on the
same clock as the device planes, and on closing records its wall time as
the ``jax.monitoring`` duration ``/fedmm/run/keys``. Read the durations
with ``jax.monitoring.register_event_duration_secs_listener``, the
timeline in xprof or Perfetto. Without a profiler or a listener a span
costs one ``TraceMe`` check and one pass over the (empty) listener list.
"""
from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def span(name: str, **args):
    """Time the body as the host phase ``name`` (dot-separated); ``args``
    (str or int) ride on the trace event. A body that raises still
    records its duration."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"fedmm.{name}", **args):
            yield
    finally:
        jax.monitoring.record_event_duration_secs(
            "/fedmm/" + name.replace(".", "/"), time.perf_counter() - t0)
