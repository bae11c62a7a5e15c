"""Ring dispatch: host milliseconds per ring step from taking the replica
state to handing it back, the program lookup and the step's launch
included (``ring.dispatch``, core/ring.py ``RingEngine.pump_async``). Its
seconds over the traced stretch ÷ its count."""
from bench.program_trace import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "ring.dispatch")
