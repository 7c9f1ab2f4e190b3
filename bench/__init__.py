"""Chip benchmark of the served KV-cache path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Cells, configurations, traffic mixes
and per-layer metrics are data found by name under this directory; the
rest (generator, statistics, FLOP count, peaks, trace reduction, plain
reference and the comparison that decides ``correct``) is the yardstick.
"""
