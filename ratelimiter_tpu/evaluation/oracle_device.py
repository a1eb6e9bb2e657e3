"""On-device exact oracle: measure sketch accuracy AT the benched operating
point, inside the benched run (fixes round-1's hardcoded accuracy claim).

The oracle is the sketch kernel itself instantiated collision-free: depth 1,
width >= n_keys (power of two), and *identity* hashing (h1 = key id,
h2 = 0, so ``col = id``). Every key gets a private cell per sub-window —
that IS an exact per-key sliding-window counter with the same time
discretization and the same in-batch greedy admission as the sketch under
test. The sketch-vs-oracle verdict disagreement is therefore *pure
collision/conservative-update error*, the quantity BASELINE.json caps at 1%
(false denies; false allows measured too and expected ~0).

Both limiters decide the same device-generated trace in one fused chunk
(evaluation/loadgen.py explains why generation is on-device), so accuracy
costs one extra kernel, not a host round-trip per decision.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.evaluation.loadgen import _splitmix64_dev, _zipf_ids
from ratelimiter_tpu.ops import sketch_kernels


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def oracle_geometry(cfg: Config, n_keys: int) -> dict:
    """Step kwargs for the collision-free oracle twin of ``cfg``."""
    return dict(sketch_kernels.step_statics(cfg), d=1, w=_next_pow2(n_keys),
                conservative=False, hh=0, hh_thresh=0.0, tenants=0)


def init_oracle_state(cfg: Config, n_keys: int) -> sketch_kernels.State:
    g = oracle_geometry(cfg, n_keys)
    return {
        "cur": jnp.zeros((1, g["w"]), jnp.int32),
        "slabs": jnp.zeros((g["S"], 1, g["w"]), jnp.int32),
        "totals": jnp.zeros((1, g["w"]), jnp.int32),
        "slab_period": jnp.full((g["S"],), sketch_kernels._NEVER, jnp.int64),
        "last_period": jnp.asarray(sketch_kernels._NEVER, jnp.int64),
    }


def build_eval_chunk(cfg: Config, B: int, n_keys: int, alpha: float) -> Callable:
    """Jitted ``chunk(states, counter0, now_us) -> (states, stats)`` deciding
    one B-sized Zipf batch with BOTH the sketch and the exact oracle.

    ``states`` is ``{"sk": sketch_state, "or": oracle_state}``; ``stats`` is
    (false_deny, false_allow, sketch_deny, oracle_deny) int64 counts.
    false_deny = sketch denied but the oracle allowed (the capped metric);
    false_allow = sketch allowed but the oracle denied.
    """
    seed = cfg.sketch.seed
    sk_kw = sketch_kernels.step_statics(cfg)
    or_kw = oracle_geometry(cfg, n_keys)

    def chunk(states, counter0, now_us):
        ids = _zipf_ids(counter0, B, n_keys, alpha)
        h = _splitmix64_dev(ids ^ jnp.uint64(seed & 0xFFFFFFFFFFFFFFFF))
        h1 = (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        h2 = (h >> jnp.uint64(32)).astype(jnp.uint32) | jnp.uint32(1)
        n = jnp.ones((B,), jnp.int32)
        sk, (sk_allow, _, _) = sketch_kernels._sketch_step(
            states["sk"], h1, h2, n, now_us, **sk_kw)
        # Oracle: identity columns (h1=id, h2=0), collision-free => exact.
        o1 = ids.astype(jnp.uint32)
        o2 = jnp.zeros((B,), jnp.uint32)
        oc, (or_allow, _, _) = sketch_kernels._sketch_step(
            states["or"], o1, o2, n, now_us, **or_kw)
        stats = (
            jnp.sum(~sk_allow & or_allow).astype(jnp.int64),
            jnp.sum(sk_allow & ~or_allow).astype(jnp.int64),
            jnp.sum(~sk_allow).astype(jnp.int64),
            jnp.sum(~or_allow).astype(jnp.int64),
        )
        return {"sk": sk, "or": oc}, stats

    return jax.jit(chunk, donate_argnums=(0,))


def build_oracle_rollover(cfg: Config, n_keys: int) -> Callable:
    g = oracle_geometry(cfg, n_keys)
    from functools import partial

    return jax.jit(partial(sketch_kernels._rollover, SW=g["SW"], S=g["S"]),
                   donate_argnums=(0,))
