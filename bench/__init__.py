"""On-chip benchmark of PaME training rounds (see BENCHMARK.json and PERF.md)."""
