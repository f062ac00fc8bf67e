"""Chip benchmark of the FSDP trainer: a data-driven harness.

Each configuration (``configs/<name>.json``), traffic mix
(``traffic/<name>.json``), cell (``workloads/<name>.json``) and per-layer
metric (``metrics/<name>.py``) lives in a file of its own, found by the
name that ``BENCHMARK.json`` gives it.  ``run.py`` is the entry point.
"""
