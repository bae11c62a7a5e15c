"""``dbs_rw_write`` kernel: its share of the HBM roofline.

Work is what the users' writes need at least, one device byte per user
byte: each 4 KiB block written to R replicas reads its payload once and
writes R blocks, (1 + R) * block bytes, counted from the blocks of write
calls completed in the traced stretch (never from the program's own byte
counts). Time is the device time of the kernel's events in the trace.
On a v5e the ``XLA Ops`` line names them by HLO text with no kernel name, e.g.
``%stepped.8 = f32[4097,32,4096]{..} custom-call(s32[64] .., s32[64] ..,
s32[2048] .., f32[4097,32,4096] .., f32[64,4096] ..),
custom_call_target="tpu_custom_call", .., output_to_operand_aliasing={{}:
(3, {})}``, one per replica per write step; ``bench.kernels.WRITE_KERNEL``
matches that calling convention. Share = work / (time * HBM bytes/s of
the chip).
"""
from bench.kernels import WRITE_KERNEL, write_user_bytes


def read(ctx):
    t = ctx.trace
    secs = t.seconds_of(WRITE_KERNEL) if t is not None else 0.0
    blocks = ctx.counters.get("write_blocks", 0)
    if secs <= 0 or not blocks:
        return None
    work = write_user_bytes(blocks, ctx.geometry)
    return 100.0 * work / (secs * ctx.peaks["hbm_bytes_per_s"])
