"""Chip benchmark of the Sprout serving path (see BENCHMARK.json, PERF.md)."""
