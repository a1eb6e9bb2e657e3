"""Contract suite instantiated for the sketch backend, plus sketch-specific
behavior (memory constancy, collision direction, sub-window sliding).

The sketch is approximate in general, but with few keys and width 65536 the
contract scenarios have no collisions, so the full exact suite runs unskipped
(exact_admission stays True here; accuracy under load is measured separately
in test_accuracy.py)."""

import numpy as np
import pytest

from tests.contract import ContractTests

from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)

class TestSketchContract(ContractTests):
    backend = "sketch"
    supports_failure_injection = True
    supports_window_scale = False  # one shared ring geometry

    def inject_failure(self, lim) -> None:
        lim.inject_failure()


def make(algo=Algorithm.TPU_SKETCH, limit=100, window=60.0, start=1_700_000_000.0,
         sketch=None, **kw):
    clock = ManualClock(start)
    cfg = Config(algorithm=algo, limit=limit, window=window,
                 sketch=sketch or SketchParams(), **kw)
    return create_limiter(cfg, backend="sketch", clock=clock), clock


class TestSketchBehavior:
    def test_memory_constant_in_keys(self):
        lim, _ = make(sketch=SketchParams(depth=4, width=1024, sub_windows=10))
        before = lim.memory_bytes()
        out = lim.allow_hashed(np.arange(5000, dtype=np.uint64))
        assert out.allow_count == 5000
        assert lim.memory_bytes() == before  # no per-key state at all
        lim.close()

    def test_sub_window_sliding_smooths_burst(self):
        # 60 sub-windows of 1s: a burst at t=59.5 still weighs ~1 at t=60.2
        lim, clock = make(limit=100, window=60.0, start=0.0)
        clock.set(59.5)
        assert lim.allow_n("k", 100).allowed
        clock.set(60.2)
        assert not lim.allow("k").allowed  # old burst still in window
        clock.set(125.0)  # > 2 windows later: fully decayed
        assert lim.allow("k").allowed
        lim.close()

    def test_decay_is_gradual_not_cliff(self):
        # With sliding sub-windows, quota returns progressively as the burst
        # ages out of the window, not all at once at the window boundary.
        lim, clock = make(limit=60, window=60.0, start=0.0)
        clock.set(30.0)
        assert lim.allow_n("k", 60).allowed
        clock.set(89.0)
        r1 = lim.allow_n("k", 60)
        assert not r1.allowed           # t-window=29 < 30: burst still counted
        clock.set(91.5)
        r2 = lim.allow_n("k", 20)
        assert r2.allowed               # burst sub-window aged out of [31.5, 91.5]
        lim.close()

    def test_overestimate_never_over_admits(self):
        # Force heavy collisions (width 16): errors must appear as extra
        # denies, never extra allows.
        lim, _ = make(limit=10, window=10.0,
                      sketch=SketchParams(depth=2, width=16, sub_windows=10))
        h = np.arange(200, dtype=np.uint64)
        out = lim.allow_hashed(h)
        # 200 distinct keys, limit 10 each: without collisions all 200 pass;
        # with collisions some are falsely denied. Over-admission impossible.
        assert out.allow_count <= 200
        per_key_second = lim.allow_hashed(h, ns=np.full(200, 11, dtype=np.int64))
        assert per_key_second.allow_count == 0  # n > limit never admitted
        lim.close()

    def test_reset_errs_toward_allowing(self):
        lim, _ = make(limit=5, window=10.0)
        for _ in range(5):
            assert lim.allow("a").allowed
        assert not lim.allow("a").allowed
        lim.reset("a")
        assert lim.allow("a").allowed
        lim.close()

    def test_prefix_namespaces_sketch(self):
        # Same key under different prefixes must not share counters.
        lim1, c1 = make(limit=3, window=60.0, key_prefix="app1")
        lim2, c2 = make(limit=3, window=60.0, key_prefix="app2")
        for _ in range(3):
            assert lim1.allow("user").allowed
        assert not lim1.allow("user").allowed
        assert lim2.allow("user").allowed  # independent namespace
        lim1.close()
        lim2.close()

    def test_hashed_and_string_paths_agree(self):
        from ratelimiter_tpu.ops.hashing import hash_strings_u64

        lim, _ = make(limit=4, window=60.0, key_prefix="")
        h = hash_strings_u64(["user:7"])
        for _ in range(4):
            assert lim.allow_hashed(h).allow_count == 1
        # Fifth through the string path: same counters, so denied.
        assert not lim.allow("user:7").allowed
        lim.close()

    def test_fixed_window_mode_resets_at_boundary(self):
        lim, clock = make(algo=Algorithm.FIXED_WINDOW, limit=5, window=10.0,
                          start=1000.0)
        assert lim.allow_n("k", 5).allowed
        assert not lim.allow("k").allowed
        clock.set(1010.5)  # next aligned window: full quota, no carryover
        assert lim.allow_n("k", 5).allowed
        lim.close()


class TestSketchTokenBucket:
    """Sketched token bucket (ops/bucket_kernels.py): reference TB semantics
    (``tokenbucket.go:23-52``) at constant memory in key cardinality."""

    def test_continuous_refill(self):
        # rate = 10/10s = 1 token/s: after draining, one token back per second.
        lim, clock = make(algo=Algorithm.TOKEN_BUCKET, limit=10, window=10.0)
        assert lim.allow_n("k", 10).allowed
        assert not lim.allow("k").allowed
        clock.advance(1.0)
        assert lim.allow("k").allowed        # exactly 1 token refilled
        assert not lim.allow("k").allowed
        clock.advance(2.5)
        assert lim.allow_n("k", 2).allowed   # 2.5 tokens: 2 whole ones spendable
        assert not lim.allow("k").allowed    # 0.5 left < 1
        lim.close()

    def test_burst_after_idle_capped_at_limit(self):
        lim, clock = make(algo=Algorithm.TOKEN_BUCKET, limit=5, window=1.0)
        assert lim.allow_n("k", 5).allowed
        clock.advance(3600.0)                # idle an hour: cap, not 18000
        assert lim.allow_n("k", 5).allowed
        assert not lim.allow("k").allowed
        lim.close()

    def test_matches_exact_backend_without_collisions(self):
        # With width 65536 and a handful of keys, the sketch holds each key
        # in private cells, and the integer decay is exact: decisions and
        # remaining match the exact oracle step for step.
        clock_s, clock_e = ManualClock(50.0), ManualClock(50.0)
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=7, window=3.0)
        sk = create_limiter(cfg, backend="sketch", clock=clock_s)
        ex = create_limiter(cfg, backend="exact", clock=clock_e)
        rng = np.random.default_rng(7)
        for _ in range(60):
            dt = float(rng.uniform(0, 1.5))
            clock_s.advance(dt)
            clock_e.advance(dt)
            key = f"user:{rng.integers(3)}"
            n = int(rng.integers(1, 4))
            rs, re = sk.allow_n(key, n), ex.allow_n(key, n)
            assert rs.allowed == re.allowed
            assert rs.remaining == re.remaining
        sk.close()
        ex.close()

    def test_collisions_only_deny(self):
        # Tiny sketch forces collisions: colliding keys share refill, so
        # errors are extra denies — never extra allows beyond n*limit.
        lim, _ = make(algo=Algorithm.TOKEN_BUCKET, limit=10, window=10.0,
                      sketch=SketchParams(depth=2, width=16))
        h = np.arange(64, dtype=np.uint64)
        out = lim.allow_hashed(h, ns=np.full(64, 10, dtype=np.int64))
        assert out.allow_count <= 64
        # Immediately after, every key's debt estimate >= its true debt:
        # nothing more may be admitted anywhere near the limit.
        again = lim.allow_hashed(h, ns=np.full(64, 10, dtype=np.int64))
        assert again.allow_count == 0
        lim.close()

    def test_retry_after_is_deficit_over_rate(self):
        # rate = 6/60s = 0.1 tokens/s; deficit of 1 token -> 10 s.
        lim, _ = make(algo=Algorithm.TOKEN_BUCKET, limit=6, window=60.0)
        assert lim.allow_n("k", 6).allowed
        res = lim.allow("k")
        assert not res.allowed
        assert res.retry_after == pytest.approx(10.0, abs=1e-5)
        lim.close()

    def test_memory_constant_in_keys(self):
        lim, _ = make(algo=Algorithm.TOKEN_BUCKET, limit=100, window=60.0,
                      sketch=SketchParams(depth=4, width=1024))
        before = lim.memory_bytes()
        out = lim.allow_hashed(np.arange(5000, dtype=np.uint64))
        assert out.allow_count == 5000
        assert lim.memory_bytes() == before
        lim.close()

    def test_windowed_kernels_reject_token_bucket_config(self):
        # Constructing the windowed SketchLimiter machinery with a
        # TOKEN_BUCKET config must raise, not silently build sliding-window
        # semantics; only the factory/SketchTokenBucketLimiter route is legal.
        from ratelimiter_tpu import InvalidConfigError
        from ratelimiter_tpu.ops import sketch_kernels

        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=5, window=10.0)
        with pytest.raises(InvalidConfigError):
            sketch_kernels.sketch_geometry(cfg)
        with pytest.raises(InvalidConfigError):
            sketch_kernels.build_hashed_step(cfg)
        with pytest.raises(InvalidConfigError):
            sketch_kernels.build_controls(cfg)

    def test_unweighted_n_greater_than_limit_never_admits(self):
        lim, _ = make(algo=Algorithm.TOKEN_BUCKET, limit=5, window=10.0)
        assert not lim.allow_n("k", 6).allowed
        assert lim.allow_n("k", 5).allowed  # denial consumed nothing
        lim.close()
