"""Replica fan-out (ICI): its share of the interconnect roofline.

Work is what the users' writes need at least: each 4 KiB block written to
R replicas on R chips leaves the engine's chip R - 1 times, one byte per
user byte (``bench.fanout.fanout_user_bytes``), counted from the blocks of
write calls completed in the traced stretch. Time is each chip's share of
the fan-out's device time (as ``replica_fanout_ms`` reads it, before the
division by steps). Share = work / (time * the chip's interconnect
bytes/s, ``ici_bytes_per_s`` in ``bench.peaks``). None where the trace
holds no fan-out, no write block completed, or the peaks give no
interconnect rate.
"""
from bench.fanout import FANOUT_OPS, fanout_user_bytes, replica_chips


def read(ctx):
    t = ctx.trace
    secs = t.seconds_of(FANOUT_OPS) if t is not None else 0.0
    blocks = ctx.counters.get("write_blocks", 0)
    ici = (ctx.peaks or {}).get("ici_bytes_per_s")
    if secs <= 0 or not blocks or not ici:
        return None
    per_chip = secs / replica_chips(ctx.geometry)
    return 100.0 * fanout_user_bytes(blocks, ctx.geometry) / (per_chip * ici)
