"""CPU tests of the benchmark harness (no chip, no topology)."""
