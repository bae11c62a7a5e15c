"""``pump_dispatch_ms`` read in the bandwidth cell, where it moves
``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.pump_dispatch_ms import read  # noqa: F401
