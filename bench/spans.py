"""Host spans recorded by the benchmark around its calls into the program.

Each span is timed by the host clock and, when a profiler trace is being
taken, also written into that trace as a ``TraceAnnotation`` named
``bench.<name>``, so a trace reduction can say what the host was doing
during a gap on the device.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict


class Spans:
    def __init__(self):
        self.total = defaultdict(float)   # name -> seconds
        self.count = defaultdict(int)
        self.annotate = False             # set while a trace is taken

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.total[name] += time.perf_counter() - t0
        self.count[name] += 1

    def reset(self):
        self.total.clear()
        self.count.clear()


class Events:
    """Durations that JAX reports through ``jax.monitoring`` (tracing,
    lowering, backend compile or compile-cache load), summed by name
    while ``on``."""

    def __init__(self):
        self.total = defaultdict(float)
        self.count = defaultdict(int)
        self.on = False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if self.on:
            self.total[name] += secs
            self.count[name] += 1

    def _event(self, name, **_):
        if self.on:
            self.count[name] += 1

    def reset(self):
        self.total.clear()
        self.count.clear()
