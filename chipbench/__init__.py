"""Chip benchmark of the serving model path (see PERF.md)."""
