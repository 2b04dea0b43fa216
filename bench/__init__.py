"""On-chip cell benchmark of TIDE (see BENCHMARK.json and PERF.md)."""
