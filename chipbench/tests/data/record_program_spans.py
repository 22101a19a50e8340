"""Record ``program_window.xplane.pb``: a small profiler trace taken on
the CPU, for the program-span reduction's test (``chipbench/spans.py``).
Inside one ``window`` span, twice: a ``submit_ingest`` span holding
``serve.admit`` (a program run and waited for) and then
``journal.append`` (a host-only sleep); a ``step`` span holding
``serve.flush`` > ``serve.dispatch`` (a program dispatched) and then
``serve.collect`` > ``qexec.sync`` (waited for) and ``qexec.finish``
(a host-only sleep); then a ``wait`` span (a sleep).

    JAX_PLATFORMS=cpu python chipbench/tests/data/record_program_spans.py
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def prog_sort(x):
    return jnp.sort(x * 2 + 1)


def main():
    a = jax.jit(prog_sort)
    x = jnp.arange(1 << 16, dtype=jnp.float32)[::-1]
    a(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("window"):
        for i in range(2):
            with TraceAnnotation("submit_ingest"):
                with TraceAnnotation("serve.admit"):
                    a(x).block_until_ready()
                with TraceAnnotation("journal.append", seq=i, bytes=4096):
                    time.sleep(0.003)
            with TraceAnnotation("step"):
                with TraceAnnotation("serve.flush", queries=2) as span:
                    span.set_metadata(groups=1, level=0)
                    with TraceAnnotation("serve.dispatch", qid=2 * i,
                                         queries=2, rows=2, slots=4):
                        y = a(x)
                with TraceAnnotation("serve.collect", qid=2 * i, queries=2):
                    with TraceAnnotation("qexec.sync", bytes=8):
                        y.block_until_ready()
                    with TraceAnnotation("qexec.finish"):
                        time.sleep(0.002)
            with TraceAnnotation("wait"):
                time.sleep(0.004)
    jax.profiler.stop_trace()
    found = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(found[0], Path(__file__).with_name("program_window.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
