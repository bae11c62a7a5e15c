"""Ring fetch: host milliseconds per ring step blocked in the
``jax.device_get`` of the step's completion view, which waits for the
device (``ring.fetch``, core/ring.py ``RingEngine._complete``). Its seconds
over the traced stretch ÷ the ``ring.dispatch`` count."""
from bench.program_trace import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "ring.fetch")
