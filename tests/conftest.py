"""Test env: JAX on the CPU with 8 virtual devices, set through the plain
environment variables BEFORE jax backends initialize.

This mirrors how the reference tests distributed behavior without a cluster
(miniredis standing in for Redis, SURVEY.md §4.2): here an 8-device CPU host
platform stands in for a v5e-8 pod so mesh/psum logic runs in CI.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

# Device backends require x64 (int64 timestamps / micro-tokens) and no
# longer flip the global at import time (ops.ensure_x64 gates instead) —
# the test env opts in here, once, before any backend initializes.
jax.config.update("jax_enable_x64", True)
# NOTE: deliberately NO persistent compile cache here (bench.py and the
# serving binary do enable one). Measured on this image, concurrent
# compilation from the stress suite's thread storms intermittently
# deadlocks inside the cache's write path (~1 in 3 full runs wedge in
# test_stress_concurrency with every thread parked on the limiter
# lock); cold compiles are slower but deterministic. Held even when the
# environment names a cache directory (server subprocesses still use it).
jax.config.update("jax_enable_compilation_cache", False)
