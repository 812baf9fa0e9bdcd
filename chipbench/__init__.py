"""The on-chip benchmark: cells of BENCHMARK.json, run by ``run.py``."""
