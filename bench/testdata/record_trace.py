"""Record ``cpu_trace.xplane.pb``, the small CPU trace that
``bench/tests/test_trace.py`` reduces:

    JAX_PLATFORMS=cpu python3 bench/testdata/record_trace.py

Three host spans named like the harness's (``bench.tick`` around two
program runs, then ``bench.wait`` around a 50 ms sleep, then another
``bench.tick``), so the trace holds device-like operations, a long idle
stretch and the host span that explains it.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.tick"):
        for _ in range(2):
            f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.wait"):
        time.sleep(0.05)
    with jax.profiler.TraceAnnotation("bench.tick"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "cpu_trace.xplane.pb"))
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
