"""Chip benchmark of the serving system: see PERF.md and BENCHMARK.json."""
