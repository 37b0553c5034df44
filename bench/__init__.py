"""Chip benchmark of the served path (see PERF.md and run.py)."""
