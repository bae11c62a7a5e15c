"""``pump_fetch_ms`` read in the bandwidth cell, where it moves
``bandwidth_mib_s`` and not ``iops``: the same reader."""
from bench.metrics.pump_fetch_ms import read  # noqa: F401
