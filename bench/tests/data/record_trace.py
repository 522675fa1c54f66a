"""Records ``small.xplane.pb``, the trace the reduction's tests read: on
one TPU, five dispatches of a small jitted program inside the benchmark's
``bench.window`` span, each under a ``bench.dispatch`` span, with a host
sleep under ``bench.sleep`` between them, so the device shows busy
intervals and idle gaps whose host activity is known.

    PYTHONPATH=. python3 bench/tests/data/record_trace.py <out.xplane.pb>
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from bench import trace_reduce


def main(out):
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=trace_reduce.options()):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(5):
                    with jax.profiler.TraceAnnotation("bench.dispatch"):
                        y = f(x)
                    y.block_until_ready()
                    with jax.profiler.TraceAnnotation("bench.sleep"):
                        time.sleep(0.002)
        shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0], out)


if __name__ == "__main__":
    main(sys.argv[1])
