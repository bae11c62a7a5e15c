"""``dbs_rw_write_roofline`` read in the bandwidth cell, where it moves
``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.dbs_rw_write_roofline import read  # noqa: F401
