"""The on-chip benchmark: one command runs one cell of ``BENCHMARK.json``.

Everything that defines the yardstick lives here, apart from the system
under test (``src/repro``): traffic generators, initial weights and data,
plain float32 references, the reduction from profiler traces to metrics,
the peak table and the operation counts. See ``bench/run.py``.
"""
