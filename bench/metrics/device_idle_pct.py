"""Device: the share of the traced stretch in which no operation ran on
the chip, 100 * (1 - busy / window), from the profiler trace: busy is the
union of the events of the ``XLA Ops`` line of ``/device:TPU:0`` (the
ring step ``jit_stepped(..)`` and the read cursor bump ``jit_add(..)`` are
the programs there)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.n_ops == 0 or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
