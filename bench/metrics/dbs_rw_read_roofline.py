"""``dbs_rw_read`` kernel: its share of the HBM roofline.

Work is what the users' reads need at least, one device byte per user
byte: each 4 KiB block read moves one block in and one block out, 2 *
block bytes, counted from the blocks of read calls completed in the traced
stretch. Time is the device time of the kernel's events in the trace.
On a v5e the ``XLA Ops`` line names them by HLO text with no kernel name, e.g.
``%stepped.9 = f32[64,1,4096]{..} custom-call(s32[64] .., s32[64] ..,
f32[4097,32,4096] ..), custom_call_target="tpu_custom_call"``, one per
replica per step that has the read phase (reads or not);
``bench.kernels.READ_KERNEL`` matches that calling convention. Share =
work / (time * HBM bytes/s of the chip).
"""
from bench.kernels import READ_KERNEL, read_user_bytes


def read(ctx):
    t = ctx.trace
    secs = t.seconds_of(READ_KERNEL) if t is not None else 0.0
    blocks = ctx.counters.get("read_blocks", 0)
    if secs <= 0 or not blocks:
        return None
    work = read_user_bytes(blocks, ctx.geometry)
    return 100.0 * work / (secs * ctx.peaks["hbm_bytes_per_s"])
