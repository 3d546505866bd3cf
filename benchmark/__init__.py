"""The benchmark: one cell, one process, one JSON line (see BENCHMARK.json)."""
