"""Ring staging: host milliseconds per ring step spent draining the
admission queues (``ring.admit``), filling the numpy lane buffers, payload
copies included (``ring.stage``), and copying them to the device
(``ring.upload``) — core/ring.py ``RingFrontend``. Those program spans'
seconds over the traced stretch ÷ the ``ring.dispatch`` count."""
from bench.program_trace import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "ring.admit", "ring.stage", "ring.upload")
