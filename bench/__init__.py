"""Chip benchmark of the ESDP dispatch engine (see PERF.md and BENCHMARK.json)."""
