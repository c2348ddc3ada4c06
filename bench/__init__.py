"""Chip benchmark of the SALAAD serving path (see PERF.md and BENCHMARK.json)."""
