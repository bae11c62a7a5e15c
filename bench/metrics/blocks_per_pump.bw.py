"""``blocks_per_pump`` read in the bandwidth cell, where it moves
``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.blocks_per_pump import read  # noqa: F401
