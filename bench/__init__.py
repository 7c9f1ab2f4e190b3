"""Chip benchmark of the served KV-cache path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Cells, configurations, traffic mixes,
per-layer metrics and model families (``families/``: the program's config,
block columns, FLOP count and plain reference of one kind of model) are
files found by name under this directory; the rest (generator, statistics,
peaks, trace reduction and the comparison that decides ``correct``) is the
yardstick.
"""
