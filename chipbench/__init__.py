"""Chip benchmark of the live serving tier: one cell of BENCHMARK.json per
run, driven by data files found by name (see chipbench/run.py)."""
