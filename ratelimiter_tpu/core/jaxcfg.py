"""Process-level JAX settings every entry point shares, in one place.

Entry points (``python -m ratelimiter_tpu.serving``, the tools) call
:func:`configure` once, before any backend initializes. Kernel code asks :func:`on_tpu` for the one
platform test the repo has. Importing this module does not import JAX.
"""

from __future__ import annotations

import os

#: The persistent compile cache when the environment names none: one
#: fixed path inside the checkout. The directory is part of the cache
#: key, so it never depends on ``~``, a pid, a time or a temp name.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> None:
    """64-bit types (the kernels' int64 state math; the library never
    flips this global at import time, see ops.ensure_x64) and the
    persistent compile cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX reads it itself and no directory is set here; platform selection
    is likewise left to plain ``JAX_PLATFORMS``."""
    import jax

    jax.config.update("jax_enable_x64", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def on_tpu() -> bool:
    """True when the default backend is a TPU — selects the table-access
    strategy (ops/sortmerge.py)."""
    import jax

    return jax.default_backend() == "tpu"
