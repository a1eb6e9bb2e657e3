"""Test env: JAX on the CPU with 8 virtual devices, set through the plain
environment variables BEFORE jax backends initialize.

This mirrors how the reference tests distributed behavior without a cluster
(miniredis standing in for Redis, SURVEY.md §4.2): here an 8-device CPU host
platform stands in for a v5e-8 pod so mesh/psum logic runs in CI.
"""

import gc
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

# Device backends require x64 (int64 timestamps / micro-tokens) and no
# longer flip the global at import time (ops.ensure_x64 gates instead) —
# the test env opts in here, once, before any backend initializes.
jax.config.update("jax_enable_x64", True)
# NOTE: deliberately NO persistent compile cache here (the serving
# binary does enable one). Measured on this image, concurrent
# compilation from the stress suite's thread storms intermittently
# deadlocks inside the cache's write path (~1 in 3 full runs wedge in
# test_stress_concurrency with every thread parked on the limiter
# lock); cold compiles are slower but deterministic. Held even when the
# environment names a cache directory (server subprocesses still use it).
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop every compiled program when a test FILE ends. Each XLA:CPU
    executable keeps several memory mappings for as long as a jit cache
    holds it, a worker that runs many files keeps them all, and a
    process may hold ``vm.max_map_count`` (65,530) mappings: measured at
    PR 49, a worker of the driver's ``-n 6 --dist loadfile`` run stood
    at 60,906 after its last-but-one file (tests/test_collective_router.py
    alone adds ~37,000) and the next compile died of a segmentation
    fault inside ``backend_compile_and_load`` — the fault ROADMAP D0
    names, in whichever test compiled next. A file's programs are its
    own (each file builds geometries of its own), so nothing is compiled
    twice that was not already; the count falls back to ~700."""
    yield
    jax.clear_caches()
    gc.collect()
