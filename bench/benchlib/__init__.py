"""Chip benchmark harness: one cell of `BENCHMARK.json` per process.

The modules here are the yardstick: traffic generation, the trace
reductions, the work functions and peaks, and the comparison that decides
`correct`. Configurations, traffic mixes and per-layer metrics are data
files and small readers beside this package, found by name; so are the
program adapters (`bench/programs/<name>.py`) and the plain references
(`bench/reference/<name>.py`) that a configuration names. Of the model's
sizes the harness reads only "task" ("cls" or "seg") and "n_points"; what
is PointNet++'s lies in those files and in the FPS and lattice work
functions of `work`.
"""
