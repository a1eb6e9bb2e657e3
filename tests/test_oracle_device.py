"""The on-device exact oracle (evaluation/oracle_device.py) must itself be
correct — it referees the headline accuracy metric. Its semantics: exact
per-key sliding window at sub-window resolution, identical time
discretization to the sketch, zero collision error."""

import numpy as np

import jax.numpy as jnp

from ratelimiter_tpu import Algorithm, Config, SketchParams
from ratelimiter_tpu.evaluation.oracle_device import (
    build_eval_chunk,
    build_oracle_rollover,
    init_oracle_state,
    oracle_geometry,
)
from ratelimiter_tpu.ops import sketch_kernels

T0 = 1_700_000_000 * 1_000_000


def _cfg(limit=5, window=6.0):
    return Config(algorithm=Algorithm.SLIDING_WINDOW, limit=limit, window=window,
                  max_batch_admission_iters=1,
                  sketch=SketchParams(depth=2, width=64, sub_windows=6))


def _oracle_step(cfg, n_keys):
    from functools import partial
    import jax

    return jax.jit(partial(sketch_kernels._sketch_step,
                           **oracle_geometry(cfg, n_keys)))


def _decide(step, st, ids, now_us, n_keys):
    h1 = jnp.asarray(np.asarray(ids, dtype=np.uint32))
    h2 = jnp.zeros(len(ids), jnp.uint32)
    n = jnp.ones(len(ids), jnp.int32)
    st, (allowed, _, _) = step(st, h1, h2, n, jnp.int64(now_us))
    return st, np.asarray(allowed)


def test_oracle_exact_per_key_admission():
    cfg = _cfg(limit=5)
    n_keys = 16
    step = _oracle_step(cfg, n_keys)
    roll = build_oracle_rollover(cfg, n_keys)
    st = roll(init_oracle_state(cfg, n_keys), jnp.int64(T0 // 1_000_000))
    # 8 requests each for keys 0 and 1 in one batch: exactly 5 admitted each,
    # the first 5 in batch order.
    ids = [0, 1] * 8
    st, allowed = _decide(step, st, ids, T0, n_keys)
    assert allowed.sum() == 10
    assert allowed[:10].all() and not allowed[10:].any()
    # Next batch: fully denied (no collision cross-talk for other keys).
    st, allowed = _decide(step, st, [0, 1, 2], T0 + 1000, n_keys)
    assert list(allowed) == [False, False, True]


def test_oracle_window_expiry():
    cfg = _cfg(limit=3, window=6.0)
    n_keys = 8
    step = _oracle_step(cfg, n_keys)
    roll = build_oracle_rollover(cfg, n_keys)
    sub_us = sketch_kernels.sketch_geometry(cfg)[1]
    st = roll(init_oracle_state(cfg, n_keys), jnp.int64(T0 // sub_us))
    st, allowed = _decide(step, st, [3, 3, 3, 3], T0, n_keys)
    assert allowed.sum() == 3
    # Two full windows later (host drives rollover, as the limiter does).
    t2 = T0 + 12_000_000
    st = roll(st, jnp.int64(t2 // sub_us))
    st, allowed = _decide(step, st, [3, 3, 3, 3], t2, n_keys)
    assert allowed.sum() == 3


def test_eval_chunk_counts_disagreements():
    """With sketch width == oracle width and identity-free hashing the
    sketch may err; the eval chunk's stats must tally exactly the
    disagreement masks. Force heavy sketch collisions (width 16) so false
    denies are certain, and check bookkeeping consistency."""
    cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=2, window=6.0,
                 max_batch_admission_iters=1,
                 sketch=SketchParams(depth=1, width=16, sub_windows=6))
    n_keys = 256
    B = 512
    chunk = build_eval_chunk(cfg, B, n_keys, 1.1)
    roll_sk = sketch_kernels.build_controls(cfg)[1]
    roll_or = build_oracle_rollover(cfg, n_keys)
    sub_us = sketch_kernels.sketch_geometry(cfg)[1]
    states = {"sk": roll_sk(sketch_kernels.init_state(cfg), jnp.int64(T0 // sub_us)),
              "or": roll_or(init_oracle_state(cfg, n_keys), jnp.int64(T0 // sub_us))}
    # Chunk 1 writes the state; collision errors surface in chunk 2 (cell
    # estimates are read pre-batch, so a single batch from empty state shows
    # no cross-key error).
    states, _ = chunk(states, jnp.uint64(0), jnp.int64(T0))
    states, stats = chunk(states, jnp.uint64(512), jnp.int64(T0 + 1000))
    fd, fa, sk_deny, or_deny = [int(np.asarray(s)) for s in stats]
    # Bookkeeping identities: disagreements bounded by deny counts.
    assert 0 <= fd <= sk_deny
    assert 0 <= fa <= or_deny
    # 16 cells shared by ~150 distinct Zipf keys at limit 2: fresh tail keys
    # read hot cells >= limit and must be falsely denied.
    assert fd > 0
    # Sketch never over-admits: anything the sketch allowed while the
    # oracle denied would be a real false allow; with depth 1 vanilla CU
    # disabled... it must stay 0 here (collisions only ADD counts).
    assert fa == 0
