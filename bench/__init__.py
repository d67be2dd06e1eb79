"""Chip benchmark of posterior-query serving: closed-loop cells, a plain
reference that decides `correct`, and readers of per-layer metrics.

Everything that decides a number lives here, apart from the program under
test (`src/repro`): traffic generation, model generation, the reduction of
profiler traces, and the reference sampler.  Cells, configurations, traffic
mixes, MRF observations and per-layer metrics are found by name from
`BENCHMARK.json` and the configuration (`bench/spec.py`).
"""
