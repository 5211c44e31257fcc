"""Chip benchmark of the autoscaler: see bench/run.py and PERF.md."""
