"""Chip benchmark harness: one cell of `BENCHMARK.json` per process.

The modules here are the yardstick: traffic generation, the trace
reductions, the work functions and peaks, and the comparison that decides
`correct`. Configurations, traffic mixes and per-layer metrics are data
files and small readers beside this package, found by name.
"""
