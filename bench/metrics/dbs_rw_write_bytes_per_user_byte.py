"""``dbs_rw_write`` kernel: HBM bytes it moves per user byte written, from
the program's counters (``VolumeManager.stats()``) over the traced
stretch: ``write_rows`` extent-row fetches and write-backs that the
kernel's grid requests (computed in the ring step from the routed vectors
the kernel receives, on every replica), each ``page_blocks`` blocks, plus
one ``batch``-block payload per ``write_kernel_calls``, in float32 lanes,
over the blocks of write calls completed."""
from bench.program_trace import write_bytes_per_user_byte as read  # noqa: F401
