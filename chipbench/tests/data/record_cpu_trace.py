"""Record ``cpu_window.xplane.pb``: a small profiler trace taken on the
CPU, for the trace reduction's test.  Inside one ``window`` span it
runs two programs (``jit_prog_sort`` three times, ``jit_prog_cumsum``
twice) with host-side waits between them, each wait in a ``wait`` span.

    JAX_PLATFORMS=cpu python chipbench/tests/data/record_cpu_trace.py
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def prog_sort(x):
    return jnp.sort(x * 2 + 1)


def prog_cumsum(x):
    return jnp.cumsum(x)


def main():
    a, b = jax.jit(prog_sort), jax.jit(prog_cumsum)
    x = jnp.arange(1 << 16, dtype=jnp.float32)[::-1]
    a(x).block_until_ready()
    b(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("step"):
                a(x).block_until_ready()
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(0.004)
            if i < 2:
                with jax.profiler.TraceAnnotation("submit_ingest"):
                    b(x).block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(found[0], Path(__file__).with_name("cpu_window.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
