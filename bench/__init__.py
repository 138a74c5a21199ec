"""The chip benchmark: one cell of ``BENCHMARK.json`` per run of ``run.py``."""
