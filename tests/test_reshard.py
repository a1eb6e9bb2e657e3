"""Elastic re-bucketing (ADR-018): split/merge per-slice state onto a
new slice count.

The pinned contracts:

* **never over-admit**: a mesh restored onto ANY other slice count
  (split, merge, prime/coprime) never allows a request the
  same-geometry restore denies — conservative-union merges only raise
  estimates;
* **overrides exact**: per-key override tables re-route exactly by
  hash across every geometry change;
* **round trip**: ``N -> k*N -> N`` is bit-identical (splits copy
  verbatim; the merge of identical copies short-circuits), and
  ``tools/rebucket.py`` round-trips a plain PR 2 durability snapshot;
* the heavy-hitter side table folds back into CMS columns on a true
  merge (counts survive, direction still deny-ward);
* the token-bucket debt slab merges with exact decay normalization.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from ratelimiter_tpu import Algorithm, Config, SketchParams
from ratelimiter_tpu.checkpoint import save_state
from ratelimiter_tpu.core.clock import ManualClock
from ratelimiter_tpu.core.errors import CheckpointError
from ratelimiter_tpu.parallel import reshard

jax = pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(cfg, clock, n):
    from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

    return SlicedMeshLimiter(cfg, clock, n_devices=n)


def _cfg(limit=20, hh_slots=0, algorithm=Algorithm.SLIDING_WINDOW):
    return Config(algorithm=algorithm, limit=limit, window=600.0,
                  sketch=SketchParams(depth=2, width=1024, sub_windows=6,
                                      hh_slots=hh_slots))


def _snapshot(lim, cfg, tmp_path, name="snap.npz"):
    kind, arrays, extra = lim.capture_state()
    path = str(tmp_path / name)
    save_state(path, kind, cfg, arrays, extra)
    return path


class TestContributors:
    def test_gcd_rule(self):
        # Clean split: one contributor (j % old_n).
        assert reshard.contributors(5, 4, 8) == [1]
        # Clean merge: the folded old slices.
        assert reshard.contributors(1, 8, 4) == [1, 5]
        # Coprime: every old slice can contribute.
        assert reshard.contributors(2, 4, 3) == [0, 1, 2, 3]
        # Same count: identity.
        assert reshard.contributors(3, 4, 4) == [3]


class TestReshardOracle:
    """N -> M restore never over-admits vs the same-geometry restore,
    and overrides survive exactly — both directions, prime M included
    (the ISSUE-11 acceptance oracle)."""

    @pytest.fixture(scope="class")
    def source(self, tmp_path_factory):
        tmp_path = tmp_path_factory.mktemp("reshard-src")
        clock = ManualClock(1000.0)
        src = _mesh(_cfg(), clock, 4)
        cfg = src.config
        rng = np.random.default_rng(7)
        keys = [f"user:{i}" for i in range(60)]
        # Uneven traffic: hot keys near/over the limit so the oracle
        # run has real denies to preserve.
        for _ in range(8):
            ks = ([keys[j] for j in rng.integers(0, 60, size=48)]
                  + keys[:6] * 2)
            src.allow_batch(ks)
            clock.advance(30.0)
        src.set_override("user:3", 5)
        src.set_override("user:7", 200)
        path = _snapshot(src, cfg, tmp_path)
        src.close()
        return cfg, clock, keys, path

    # 8 = clean split (verbatim copies), 3 = prime merge (every old
    # slice contributes — the all-contributors CRT shape; the clean
    # 2-merge is a strict subset of its logic).
    @pytest.mark.parametrize("m", [8, 3])
    def test_never_over_admits_and_overrides_exact(self, source, m):
        cfg, clock, keys, path = source
        oracle = _mesh(cfg, ManualClock(clock.now()), 4)
        oracle.restore(path)
        dst = _mesh(cfg, ManualClock(clock.now()), m)
        dst.restore(path)
        try:
            assert dst.get_override("user:3").limit == 5
            assert dst.get_override("user:7").limit == 200
            assert dst.override_count() == oracle.override_count()
            ro = oracle.allow_batch(keys)
            rd = dst.allow_batch(keys)
            over = rd.allowed & ~ro.allowed
            assert not over.any(), (
                f"resharded 4->{m} mesh over-admits {int(over.sum())} "
                f"key(s) vs the same-geometry source")
            # The oracle traffic must actually contain denies, or the
            # assertion above is vacuous.
            assert not ro.allowed.all()
        finally:
            oracle.close()
            dst.close()

    def test_split_then_merge_round_trip_bit_identical(self, source,
                                                       tmp_path):
        cfg, clock, _, path = source
        mid = _mesh(cfg, ManualClock(clock.now()), 8)
        mid.restore(path)
        p8 = _snapshot(mid, cfg, tmp_path, "snap8.npz")
        mid.close()
        back = _mesh(cfg, ManualClock(clock.now()), 4)
        back.restore(p8)
        p4 = _snapshot(back, cfg, tmp_path, "snap4.npz")
        back.close()
        with np.load(path, allow_pickle=False) as a, \
                np.load(p4, allow_pickle=False) as b:
            names = [k for k in a.files if not k.startswith("__")]
            assert set(names) == {k for k in b.files
                                  if not k.startswith("__")}
            for k in names:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)

    def test_restore_slice_refusal_names_rebucket_path(self, source):
        cfg, clock, _, path = source
        dst = _mesh(cfg, ManualClock(clock.now()), 3)
        try:
            with pytest.raises(CheckpointError) as ei:
                dst.restore_slice(path, 0)
            msg = str(ei.value)
            assert "rebucket" in msg and "restore()" in msg
        finally:
            dst.close()


class TestHeavyHitterFold:
    def test_merge_folds_hh_counts_never_over_admits(self, tmp_path):
        clock = ManualClock(1000.0)
        src = _mesh(_cfg(hh_slots=16), clock, 4)
        cfg = src.config
        # Hammer one key so it promotes into the side table, then keep
        # hammering: its exact count lives in hh cells, not the CMS.
        hot = "tenant:hot"
        for _ in range(6):
            src.allow_batch([hot] * 4)
            clock.advance(20.0)
        path = _snapshot(src, cfg, tmp_path)
        src.close()
        oracle = _mesh(cfg, ManualClock(clock.now()), 4)
        oracle.restore(path)
        merged = _mesh(cfg, ManualClock(clock.now()), 2)
        merged.restore(path)
        try:
            ro = oracle.allow_n(hot, 1)
            rm = merged.allow_n(hot, 1)
            # The fold keeps the promoted key's mass: if the source
            # denies, the merged mesh must deny too.
            assert not ro.allowed
            assert not rm.allowed
        finally:
            oracle.close()
            merged.close()


class TestTokenBucketReshard:
    def test_debt_merge_never_over_admits(self, tmp_path):
        clock = ManualClock(1000.0)
        src = _mesh(_cfg(limit=10, algorithm=Algorithm.TOKEN_BUCKET),
                    clock, 4)
        cfg = src.config
        ids = np.arange(48, dtype=np.uint64)
        rng = np.random.default_rng(3)
        for _ in range(4):
            src.allow_ids(ids[rng.integers(0, 48, size=96)]
                          .astype(np.uint64))
            clock.advance(0.5)
        path = _snapshot(src, cfg, tmp_path)
        src.close()
        for m in (3,):  # prime merge — the all-contributors shape
            oracle = _mesh(cfg, ManualClock(clock.now()), 4)
            oracle.restore(path)
            dst = _mesh(cfg, ManualClock(clock.now()), m)
            dst.restore(path)
            try:
                ro = oracle.allow_ids(ids)
                rd = dst.allow_ids(ids)
                over = rd.allowed & ~ro.allowed
                assert not over.any(), f"4->{m} bucket over-admits"
                assert not ro.allowed.all()
            finally:
                oracle.close()
                dst.close()

    def test_decay_normalization_is_exact_mirror(self):
        from ratelimiter_tpu.ops import bucket_kernels

        cfg = _mesh(_cfg(limit=10, algorithm=Algorithm.TOKEN_BUCKET),
                    ManualClock(0.0), 1).config
        kw = bucket_kernels.step_statics(cfg)
        num, den = kw["rate_num"], kw["rate_den"]
        import jax.numpy as jnp

        for elapsed, rem in [(0, 0), (123456, 17), (10**9, den - 1),
                             (10**13, 0)]:
            host = reshard._decay_exact(elapsed, rem, num, den)
            dev, _ = bucket_kernels._decay(
                {"last": jnp.asarray(0, jnp.int64),
                 "rem": jnp.asarray(rem, jnp.int64)},
                jnp.asarray(elapsed, jnp.int64),
                rate_num=num, rate_den=den)
            assert host == int(dev), (elapsed, rem)


class TestMergeStates:
    def test_identical_states_short_circuit_verbatim(self):
        clock = ManualClock(1000.0)
        from ratelimiter_tpu.algorithms.sketch import SketchLimiter

        lim = SketchLimiter(_cfg(), clock)
        lim.allow_batch([f"k{i}" for i in range(32)])
        _, arrays, extra = lim.capture_state()
        merged, _ = reshard.merge_states(
            [dict(arrays), dict(arrays), dict(arrays)],
            [dict(extra)] * 3)
        for k in arrays:
            np.testing.assert_array_equal(np.asarray(arrays[k]),
                                          merged[k], err_msg=k)

    def test_merge_into_limiter_carries_counters_and_overrides(self):
        clock = ManualClock(1000.0)
        from ratelimiter_tpu.algorithms.sketch import SketchLimiter

        src = SketchLimiter(_cfg(), clock)
        cfg = src.config
        for _ in range(20):
            src.allow_n("hot", 1)
        src.set_override("vip", 3)
        _, arrays, extra = src.capture_state()
        dst = SketchLimiter(cfg, clock)
        for _ in range(4):
            dst.allow_n("other", 1)
        reshard.merge_into_limiter(dst, arrays, extra)
        assert not dst.allow_n("hot", 1).allowed
        assert dst.get_override("vip").limit == 3
        # The destination's own traffic survives the fold too.
        r = dst.allow_n("other", 1)
        assert r.allowed and r.remaining <= cfg.limit - 5


class TestRebucketTool:
    def test_cli_round_trips_a_plain_pr2_snapshot(self, tmp_path):
        """tools/rebucket.py round-trips the PR 2 durability format:
        plain -> 3-slice mesh -> plain, bit-identical, and both
        intermediate forms restore into live limiters."""
        from ratelimiter_tpu.algorithms.sketch import SketchLimiter
        from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

        clock = ManualClock(1000.0)
        lim = SketchLimiter(_cfg(), clock)
        cfg = lim.config
        lim.allow_batch([f"k{i}" for i in range(40)])
        lim.set_override("vip", 9)
        plain = _snapshot(lim, cfg, tmp_path, "plain.npz")
        mesh3 = str(tmp_path / "mesh3.npz")
        back = str(tmp_path / "back.npz")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # One leg through the real CLI (argv contract); the return leg
        # calls the same entry in-process (a second interpreter boot
        # would buy nothing but tier-1 seconds).
        subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "rebucket.py"),
             plain, mesh3, "--slices", "3"], check=True, env=env)
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import rebucket as rebucket_cli

            assert rebucket_cli.main([mesh3, back, "--slices", "1"]) == 0
        finally:
            sys.path.remove(os.path.join(REPO, "tools"))
        with np.load(plain, allow_pickle=False) as a, \
                np.load(back, allow_pickle=False) as b:
            for k in [k for k in a.files if not k.startswith("__")]:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)
        m = SlicedMeshLimiter(cfg, ManualClock(clock.now()), n_devices=3)
        m.restore(mesh3)
        assert m.get_override("vip").limit == 9
        m.close()
        p = SketchLimiter(cfg, ManualClock(clock.now()))
        p.restore(back)
        assert p.get_override("vip").limit == 9

    def test_cli_rejects_bad_slices(self, tmp_path):
        rc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "rebucket.py"),
             "in.npz", "out.npz", "--slices", "0"],
            capture_output=True).returncode
        assert rc != 0
