"""``api_us_per_call`` read in the bandwidth cell, where it moves
``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.api_us_per_call import read  # noqa: F401
