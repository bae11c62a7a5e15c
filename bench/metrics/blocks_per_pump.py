"""Ring admission: block requests completed per ring step dispatched
(``RingFrontend`` batch occupancy, at most the batch width), from the
engine's own counters ``completed`` and ``dispatches`` over the traced
stretch."""


def read(ctx):
    steps = ctx.counters.get("dispatches", 0)
    return ctx.counters["completed"] / steps if steps else None
