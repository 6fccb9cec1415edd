"""Chip benchmark of DC-kCore: ``python3 bench/run.py --workload <cell>``.

Cells, configurations, traffic mixes and metrics are found by name from
``BENCHMARK.json``; see ``run.py``.
"""
