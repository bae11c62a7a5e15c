"""Ring pump: host milliseconds per ``VolumeManager.pump()`` — admission,
staging, the ring step's dispatch, the blocking fetch of the completions
and their delivery (``core/ring.py``). Mean of the ``bench.pump`` spans of
the traced stretch."""


def read(ctx):
    mean = ctx.span_mean("pump")
    return None if mean is None else 1e3 * mean
