"""Chip benchmark of the simulator's studies: ``python chip_bench/run.py``.

``BENCHMARK.json`` at the repository root names the cells; everything a
cell needs is found by name under this directory: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<cell>.json``, the study driver
``studies/<kind>.py`` and one reader per per-layer metric,
``metrics/<metric>.py``.
"""
