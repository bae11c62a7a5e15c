"""Byte API: host microseconds per ``Volume.pread`` / ``Volume.pwrite``
call, the time to fan a call out into block ``Request``s and queue them
(``core/blockdev.py``). Mean of the ``bench.submit`` spans of the traced
stretch."""


def read(ctx):
    mean = ctx.span_mean("submit")
    return None if mean is None else 1e6 * mean
