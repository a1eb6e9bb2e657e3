"""Device-side synthetic load generation + pipelined decision runner.

Device-saturation runs (bench.py phases A-C) must not be bounded by
uploading 8 bytes of hashed key per decision, so this module keeps the
*system under test* identical — the same sketch step kernel the limiter
dispatches — but synthesizes the request trace on device. It bypasses
the served path on purpose; a served number comes from a real server
(chip_smoke.py, ROADMAP S1):

* uniform u64 stream via the splitmix64 finalizer over a counter (same
  mixer as ops/hashing.py, vectorized integer ops);
* bounded-Pareto inverse CDF maps uniforms to Zipf(alpha)-distributed key
  ids over [0, n_keys) (the continuous analog of the discrete Zipf used by
  evaluation.accuracy — same skew shape, closed form, no lookups);
* ids are hashed to (h1, h2) exactly like real ingest, then decided by
  ops.sketch_kernels._sketch_step; verdicts come back as packed bitmasks
  (1 bit/decision) so readback stays off the critical path.

BASELINE config 3 is expressed this way: batch=4096 ingest batches are
coalesced into one mega-batch device dispatch (the micro-batcher's
behavior at saturation), with full in-batch same-key sequencing — a
*stronger* atomicity story than deciding 4096-slices against stale
snapshots.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.ops import sketch_kernels


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


def build_bench_chunk(cfg: Config, B: int, n_keys: int, alpha: float) -> Callable:
    """Jitted ``chunk(state, counter0, now_us) -> (state, packed, denies)``:
    generate B Zipf requests on device, decide them in one sketch step,
    return the packed allow bitmask + deny count. State is donated (stays
    resident in HBM)."""
    step_kw = sketch_kernels.step_statics(cfg)
    seed = cfg.sketch.seed

    def chunk(state, counter0, now_us):
        ids = _zipf_ids(counter0, B, n_keys, alpha)
        h = _splitmix64_dev(ids ^ jnp.uint64(seed & 0xFFFFFFFFFFFFFFFF))
        h1 = (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        h2 = (h >> jnp.uint64(32)).astype(jnp.uint32) | jnp.uint32(1)
        n = jnp.ones((B,), jnp.int32)
        state, (allowed, _rem, _est) = sketch_kernels._sketch_step(
            state, h1, h2, n, now_us, **step_kw)
        packed = sketch_kernels._pack_bits(allowed)
        denies = jnp.sum(~allowed).astype(jnp.int32)
        return state, packed, denies

    return jax.jit(chunk, donate_argnums=(0,))
