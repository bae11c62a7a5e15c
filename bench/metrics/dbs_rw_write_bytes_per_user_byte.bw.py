"""``dbs_rw_write_bytes_per_user_byte`` read in the bandwidth cell, where
it moves ``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.dbs_rw_write_bytes_per_user_byte import read  # noqa: F401
