"""Ring completion: host milliseconds per ring step delivering each
lane's status and result and requeueing the lanes not admitted
(``ring.deliver``, core/ring.py ``RingEngine._complete``). Its seconds over
the traced stretch ÷ the ``ring.dispatch`` count."""
from bench.program_trace import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "ring.deliver")
