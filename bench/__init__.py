"""Chip benchmark of the FP4 serving engine: one harness driven by data.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Configurations (``configs/``), traffic
mixes (``traffic/``), per-layer metric readers (``metrics/``) and plain
references (``reference/``) are found by name, so a new cell is new files.
"""
