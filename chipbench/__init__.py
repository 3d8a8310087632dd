"""Chip benchmark: one data-driven harness over cells named in BENCHMARK.json."""
