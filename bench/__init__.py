"""On-chip benchmark of the serve engine (see BENCHMARK.json and PERF.md)."""
