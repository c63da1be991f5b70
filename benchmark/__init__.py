"""The chip benchmark: BENCHMARK.json names its command, cells and metrics."""
