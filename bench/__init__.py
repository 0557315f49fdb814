"""On-chip benchmark of the screened graphical-lasso engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once on the chip it is started on.
Everything a cell needs is found by name: ``configs/<config>.json``,
``workloads/<cell>.json``, ``traffic/<kind>.py``, ``metrics/<metric>.py``
and ``data/<generator>.py``.  A new cell, configuration, traffic kind or
per-layer metric is a new file.
"""
