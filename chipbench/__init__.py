"""Chip benchmark of the Bayesian decision engine (see BENCHMARK.json and PERF.md)."""
