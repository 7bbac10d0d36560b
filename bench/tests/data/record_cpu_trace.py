"""Records ``cpu_trace.xplane.pb``: three jobs of a small jitted program on
the CPU, each inside the benchmark's host spans, with a pause between jobs.

    JAX_PLATFORMS=cpu python bench/tests/data/record_cpu_trace.py
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.entry"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.block"):
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.readback"):
                float(y)
            time.sleep(0.005)
        jax.profiler.stop_trace()
        src = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        shutil.copy(src, Path(__file__).with_name("cpu_trace.xplane.pb"))


if __name__ == "__main__":
    main()
