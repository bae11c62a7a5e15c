"""Hazard fence: ring steps dispatched inside the flushes the byte API's
overlapping-block fence forces (core/blockdev.py ``_fence_write``), per
thousand calls completed, from the program's counter ``fence_steps``
(``VolumeManager.stats()``) over the traced stretch."""
from bench.program_trace import fence_steps_per_kcall as read  # noqa: F401
