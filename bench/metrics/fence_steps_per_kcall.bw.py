"""``fence_steps_per_kcall`` read in the bandwidth cell, where it moves
``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.fence_steps_per_kcall import read  # noqa: F401
