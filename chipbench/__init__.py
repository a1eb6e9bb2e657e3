"""chipbench — the benchmark of ratelimiter_tpu's served path on the chip.

BENCHMARK.json at the root of the repo names the cells; everything that
belongs to one configuration, one traffic mix or one per-layer metric is
a file of its own under this directory (see README.md).
"""
