"""``device_idle_pct`` read in the bandwidth cell, where it moves
``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.device_idle_pct import read  # noqa: F401
