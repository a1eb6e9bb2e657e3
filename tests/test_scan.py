"""build_scan (the multi-step lax.scan runner used by bench phase C and the
micro-batching server): equivalence to single-step dispatches, bit-packing,
and the sub-window-boundary precondition (ADVICE r1)."""

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ratelimiter_tpu import Algorithm, Config, SketchParams
from ratelimiter_tpu.ops import sketch_kernels


def _cfg(**kw):
    base = dict(algorithm=Algorithm.SLIDING_WINDOW, limit=5, window=6.0,
                max_batch_admission_iters=1,
                sketch=SketchParams(depth=2, width=256, sub_windows=6))
    base.update(kw)
    return Config(**base)


def _fresh(cfg, now_us):
    _, sub_us, _, _, _ = sketch_kernels.sketch_geometry(cfg)
    _, roll = sketch_kernels.build_controls(cfg)
    return roll(sketch_kernels.init_state(cfg), jnp.int64(now_us // sub_us))


def _unpack(packed, B):
    bits = np.unpackbits(np.asarray(packed).astype(np.uint8).reshape(-1, B // 8),
                         axis=1, bitorder="little")
    return bits.astype(bool)


T0 = 1_700_000_000 * 1_000_000


def test_scan_equals_sequential_steps():
    cfg = _cfg()
    step = jax.jit(partial(sketch_kernels._sketch_step,
                           **sketch_kernels.step_statics(cfg)))
    scan = sketch_kernels.build_scan(cfg)
    T, B = 4, 8
    rng = np.random.default_rng(3)
    h1 = rng.integers(0, 2 ** 32, size=(T, B), dtype=np.uint32)
    h2 = rng.integers(0, 2 ** 32, size=(T, B), dtype=np.uint32) | 1
    ns = np.ones((T, B), np.int32)
    dt = 1000  # 1 ms steps, all within one 1 s sub-window

    st = _fresh(cfg, T0)
    st, packed, denies = scan(st, jnp.asarray(h1), jnp.asarray(h2),
                              jnp.asarray(ns), jnp.int64(T0), jnp.int64(dt))
    got = _unpack(packed, B)

    st2 = _fresh(cfg, T0)
    want = []
    for t in range(T):
        st2, (allowed, _, _) = step(st2, jnp.asarray(h1[t]), jnp.asarray(h2[t]),
                                    jnp.asarray(ns[t]), jnp.int64(T0 + t * dt))
        want.append(np.asarray(allowed))
    np.testing.assert_array_equal(got, np.stack(want))
    np.testing.assert_array_equal(np.asarray(denies),
                                  (~np.stack(want)).sum(axis=1))
    # Final states agree too.
    for k in ("cur", "totals"):
        np.testing.assert_array_equal(np.asarray(st[k]), np.asarray(st2[k]))


def test_scan_boundary_precondition_clamps_conservatively():
    """A chunk that crosses a sub-window boundary violates the documented
    precondition. The kernel's clamp (now = max(now, period start)) freezes
    time at the stale period rather than reading rotated state: counts keep
    accumulating in the old sub-window — the error direction is toward
    MORE denies, never over-admission."""
    cfg = _cfg(limit=3)
    scan = sketch_kernels.build_scan(cfg)
    T, B = 3, 8
    h1 = np.full((T, B), 12345, dtype=np.uint32)
    h2 = np.full((T, B), 99991, dtype=np.uint32)
    ns = np.ones((T, B), np.int32)
    st = _fresh(cfg, T0)
    # dt of one full sub-window: steps 2 and 3 land in later periods.
    _, sub_us, _, _, _ = sketch_kernels.sketch_geometry(cfg)
    st, packed, _ = scan(st, jnp.asarray(h1), jnp.asarray(h2), jnp.asarray(ns),
                         jnp.int64(T0), jnp.int64(sub_us))
    got = _unpack(packed, B)
    # limit=3 total admitted across the whole chunk: no quota "refresh" from
    # the skipped rollovers is ever granted.
    assert got.sum() == 3


def test_dense_scan_equals_sequential_steps():
    """dense_kernels.build_scan (benchmark device-time shape): T scanned
    steps produce bit-identical decisions and state to T single-step
    dispatches, for every algorithm."""
    from ratelimiter_tpu.ops import dense_kernels

    for algo in (Algorithm.FIXED_WINDOW, Algorithm.SLIDING_WINDOW,
                 Algorithm.TOKEN_BUCKET):
        cfg = Config(algorithm=algo, limit=5, window=6.0,
                     max_batch_admission_iters=1)
        step = dense_kernels.build_step(cfg)
        scan = dense_kernels.build_scan(cfg)
        T, B, cap = 4, 8, 16
        rng = np.random.default_rng(9)
        sids = rng.integers(0, cap, size=(T, B)).astype(np.int32)
        ns = np.ones((T, B), np.int64)
        dt = 1000

        st = dense_kernels.init_state(algo, cap, cfg.limit)
        st, packed, denies = scan(st, jnp.asarray(sids), jnp.asarray(ns),
                                  jnp.int64(T0), jnp.int64(dt))
        got = _unpack(packed, B)

        st2 = dense_kernels.init_state(algo, cap, cfg.limit)
        want = []
        for t in range(T):
            st2, (allowed, *_rest) = step(st2, jnp.asarray(sids[t]),
                                          jnp.asarray(ns[t]),
                                          jnp.int64(T0 + t * dt))
            want.append(np.asarray(allowed))
        np.testing.assert_array_equal(got, np.stack(want), err_msg=str(algo))
        np.testing.assert_array_equal(np.asarray(denies),
                                      (~np.stack(want)).sum(axis=1))
        for k in st:
            np.testing.assert_array_equal(np.asarray(st[k]),
                                          np.asarray(st2[k]),
                                          err_msg=f"{algo} {k}")


def test_pack_bits_roundtrip():
    mask = np.array([True, False, True, True, False, False, True, False,
                     True, True, True, True, False, False, False, True])
    packed = np.asarray(sketch_kernels._pack_bits(jnp.asarray(mask)))
    np.testing.assert_array_equal(_unpack(packed[None], 16)[0], mask)
