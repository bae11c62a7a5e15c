"""Record ``cpu_trace_program.xplane.pb``, the small trace the tests of
``bench/program_trace.py`` read: the benchmark's spans with the program's
own spans nested inside them. Fed on standard input, so that the trace
records no source path, and run from this directory, where it writes the
trace:

    cd tests/bench/data && \\
        JAX_PLATFORMS=cpu python - < record_cpu_trace_program.py

Inside one ``bench.window`` span it runs three rounds of ``bench.submit``,
``bench.pump`` and ``bench.harvest``. Each ``bench.pump`` holds the ring
spans of one step: ``ring.admit`` and ``ring.stage`` (the host sleeps),
``ring.upload`` (a small jitted op), ``ring.dispatch`` (a jitted matrix
product launched), ``ring.fetch`` (waits for it, then the host sleeps 25
ms with the device idle) and ``ring.deliver`` (a small jitted op, then a
sleep). In the second round ``ring.stage`` holds a ``py.gc`` span of 40
ms; in the third, ``bench.submit`` holds a ``vm.fence`` span with a step
of its own. So the longest idle gaps read ``bench.pump>ring.stage>py.gc``,
``bench.submit>vm.fence>ring.fetch`` and ``bench.pump>ring.fetch``.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = "cpu_trace_program.xplane.pb"
span = jax.profiler.TraceAnnotation


def main() -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda x: x[:8] + 1.0)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    step = 0

    def ring_step() -> None:
        nonlocal step
        step += 1
        with span("ring.dispatch", step=step):
            out = f(x)
        with span("ring.fetch", step=step):
            out.block_until_ready()
            time.sleep(0.025)

    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with span("bench.window"):
        for r in range(3):
            with span("bench.submit"):
                if r == 2:
                    time.sleep(0.008)
                    with span("vm.fence"):
                        ring_step()
                time.sleep(0.005 if r == 2 else 0.020)
            with span("bench.pump"):
                with span("ring.admit"):
                    time.sleep(0.001)
                with span("ring.stage"):
                    time.sleep(0.002)
                    if r == 1:
                        with span("py.gc", generation=2):
                            time.sleep(0.040)
                with span("ring.upload"):
                    g(x).block_until_ready()
                ring_step()
                with span("ring.deliver"):
                    g(x).block_until_ready()
                    time.sleep(0.002)
            with span("bench.harvest"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, OUT)
    shutil.rmtree(d)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
