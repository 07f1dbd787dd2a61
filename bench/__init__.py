"""Chip benchmark for the serving engine: one cell per run, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Cells, configurations, traffic mixes and per-layer metrics are found by
the names in ``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json`` and ``bench/metrics/<metric>.py``.
"""
