"""Device-side ops: the TPU-native replacement for the reference's Lua kernels.

The reference's atomic compute unit is a Lua script executed inside Redis
(``fixedwindow.go:21-27``, ``slidingwindow.go:22-30``, ``tokenbucket.go:23-52``
— SURVEY.md §2.2). Here the atomic unit is a fused, jitted batched step:
static shapes, no data-dependent Python control flow, int64 micro-units for
drift-free token accounting, and sort+segment-scan sequencing so one batch
behaves like the same requests serialized through Redis.
"""

from __future__ import annotations

from functools import partial


def ensure_x64() -> None:
    """The device kernels do exact integer state math in int64 microseconds
    and micro-tokens; without jax_enable_x64 those arrays silently truncate
    to int32 and every timestamp/level computation is wrong.

    Importing this library does NOT flip the flag for the whole process
    (that global would change the dtype semantics of unrelated user JAX
    code); instead every kernel factory calls this and fails loudly so the
    embedding process opts in explicitly.
    """
    import jax

    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "ratelimiter_tpu device backends require 64-bit JAX types: call "
            "jax.config.update('jax_enable_x64', True) (or set the "
            "JAX_ENABLE_X64=1 env var) before creating a dense/sketch "
            "limiter. The exact (host) backend works without it.")


def named(name: str, fn, **static):
    """``functools.partial(fn, **static)`` under a name. ``jax.jit``
    names the compiled module after its function (``jit_<name>``) and a
    bare partial has none, so every step was ``jit__unknown`` in a
    profile. Metadata only: the traced program is the same."""
    bound = partial(fn, **static)
    bound.__name__ = name
    return bound


def memoized(cache: dict, statics: dict, extra: tuple, build):
    """``build()`` once per (statics, extra), remembered in ``cache``.
    The key is COMPUTED from the keyword mapping a program is built with
    — its sorted items — plus what the builder binds besides (seed,
    premix, mesh, ...), never spelled by hand next to it: a static the
    step reads cannot be left out of the key, so ``update_limit`` can
    never be served a program compiled for the old limit."""
    key = (tuple(sorted(statics.items())), *extra)
    built = cache.get(key)
    if built is None:
        built = cache[key] = build()
    return built
