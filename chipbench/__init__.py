"""Chip benchmark of the served DiffServe cascade.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU that
the process finds, and prints one JSON result line. Configurations,
traffic mixes and per-layer metric readers are found by name under
``configs/``, ``traffic/`` and ``metrics/``.
"""
