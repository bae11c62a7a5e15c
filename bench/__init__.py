"""The chip benchmark of the byte-addressed ``VolumeManager`` ring path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip it is started
on. Everything a cell needs is found by name: its deployment in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<mix>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""
