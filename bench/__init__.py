"""The on-chip benchmark: one command runs one cell of BENCHMARK.json once.

See bench/run.py for the command line and PERF.md for what each cell,
metric and limit means.
"""
