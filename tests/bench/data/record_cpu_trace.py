"""Record ``cpu_trace.xplane.pb``, the small trace the trace-reduction
tests read. Fed on standard input, so that the trace records no source
path, and run from this directory, where it writes the trace:

    cd tests/bench/data && JAX_PLATFORMS=cpu python - < record_cpu_trace.py

Inside one ``bench.window`` span it runs three rounds of: ``bench.submit``
(the host sleeps 20 ms, nothing runs), ``bench.pump`` (a jitted matrix
product, waited for) and ``bench.harvest`` (the host sleeps 5 ms). So the
longest idle gaps fall in ``bench.submit`` and the device operations in
``bench.pump``.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = "cpu_trace.xplane.pb"


def main() -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.submit"):
                time.sleep(0.020)
            with jax.profiler.TraceAnnotation("bench.pump"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.harvest"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, OUT)
    shutil.rmtree(d)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
