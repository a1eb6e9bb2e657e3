"""Device-side synthetic request streams.

A run that saturates the device must not be bounded by uploading 8 bytes
of hashed key per decision, so the request trace is synthesized on the
device (the caller is evaluation/oracle_device.py; a served number comes
from a real server — chipbench, chip_smoke.py):

* uniform u64 stream via the splitmix64 finalizer over a counter (same
  mixer as ops/hashing.py, vectorized integer ops);
* bounded-Pareto inverse CDF maps uniforms to Zipf(alpha)-distributed key
  ids over [0, n_keys) (the continuous analog of the discrete Zipf used by
  evaluation.accuracy — same skew shape, closed form, no lookups).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _splitmix64_dev(x: jnp.ndarray) -> jnp.ndarray:
    """Vectorized splitmix64 finalizer on device (uint64; TPU emulates
    64-bit integer ops with 32-bit pairs — still ~ns/element, negligible
    next to the decision kernel)."""
    x = x + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _zipf_ids(counter0: jnp.ndarray, B: int, n_keys: int, alpha: float) -> jnp.ndarray:
    """(B,) uint64 Zipf(alpha)-distributed ids in [0, n_keys): bounded-Pareto
    inverse CDF, x = (1 + u*((N+1)^(1-a) - 1))^(1/(1-a))."""
    ctr = counter0 + jax.lax.iota(jnp.uint64, B)
    u64 = _splitmix64_dev(ctr)
    u = (u64 >> jnp.uint64(40)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    a1 = 1.0 - alpha                       # < 0
    hi = float((n_keys + 1) ** a1)
    x = jnp.exp(jnp.log1p(u * jnp.float32(hi - 1.0)) * jnp.float32(1.0 / a1))
    ids = jnp.clip(x.astype(jnp.int64) - 1, 0, n_keys - 1)
    return ids.astype(jnp.uint64)
