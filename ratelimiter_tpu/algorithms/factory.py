"""Limiter factory — the constructor seam.

Reference parity: ``NewTokenBucket`` / ``NewSlidingWindow`` / ``NewFixedWindow``
(``tokenbucket.go:63``, ``slidingwindow.go:41``, ``fixedwindow.go:38``) each
validate config and return the interface type. Here one factory selects both
the algorithm (Config.algorithm) and the state backend:

* ``exact``  — host dict, exact semantics, the oracle (algorithms/exact.py).
* ``dense``  — JAX device arrays, a state row a key behind a device-resident
  key directory, batched kernels on the hashed, pipelined lane. With
  ``Config.mesh.devices`` (or the ``n_devices`` kwarg) set: one such table
  per chip, a key's row on the chip that owns its hash (the slice-parallel
  tier with exact slices; host router only).
* ``sketch`` — count-min sketch + sub-window decay on device; approximate,
  unbounded keys (the BASELINE.json north star).
* ``mesh``   — slice-parallel serving over every visible device (ADR-012):
  one device-pinned sketch (or sketched token-bucket) slice per chip, keys
  hash-routed to their owning slice, decide path collective-free. Cap the
  device count via ``Config.mesh.devices`` or the ``n_devices`` kwarg.
"""

from __future__ import annotations

from typing import Optional

from ratelimiter_tpu.core.clock import Clock
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.core.errors import InvalidConfigError
from ratelimiter_tpu.core.types import Algorithm
from ratelimiter_tpu.algorithms.base import RateLimiter

BACKENDS = ("exact", "dense", "sketch", "mesh")


def create_limiter(
    config: Config,
    backend: str = "exact",
    clock: Optional[Clock] = None,
    **kwargs,
) -> RateLimiter:
    """Build a limiter. Validation happens in the RateLimiter constructor
    (reference shape: validate-then-construct, ``tokenbucket.go:63-81``);
    no device or I/O work happens until the first decision."""
    if backend == "exact":
        from ratelimiter_tpu.algorithms.exact import ExactLimiter

        return ExactLimiter(config, clock)
    if backend == "dense":
        if config.mesh.devices is not None or "n_devices" in kwargs:
            if config.mesh.router == "collective":
                # The routed step neither donates its state nor may
                # select between an old and a new 2 GB leaf
                # (ops/route_kernels.py): sketch slices only.
                raise InvalidConfigError(
                    "the collective router cannot carry dense slices; "
                    "use router='host'")
            from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

            return SlicedMeshLimiter(config, clock, backend="dense",
                                     **kwargs)
        from ratelimiter_tpu.algorithms.dense import DenseLimiter

        return DenseLimiter(config, clock, **kwargs)
    if backend == "sketch":
        if config.algorithm is Algorithm.TOKEN_BUCKET:
            from ratelimiter_tpu.algorithms.sketch import SketchTokenBucketLimiter

            return SketchTokenBucketLimiter(config, clock, **kwargs)
        from ratelimiter_tpu.algorithms.sketch import SketchLimiter

        return SketchLimiter(config, clock, **kwargs)
    if backend == "mesh":
        if config.mesh.router == "collective":
            # Collective mesh router (ADR-024): same slices, same owner
            # rule, but every frame is ONE shard_map'd SPMD dispatch.
            from ratelimiter_tpu.parallel.collective import (
                CollectiveMeshLimiter,
            )

            return CollectiveMeshLimiter(config, clock, **kwargs)
        from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

        return SlicedMeshLimiter(config, clock, **kwargs)
    raise InvalidConfigError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
