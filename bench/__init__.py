"""Chip benchmark of the serving path: see bench/harness.py and PERF.md."""
