"""Collective mesh router parity suite (ADR-024).

``MeshSpec.router="collective"`` makes a mixed frame ONE shard_map'd
SPMD dispatch: owners computed on device (same ``h64 % n`` rule as the
host router), rows binned and routed with ``jax.lax.all_to_all``, the
existing fused decision kernels run on owned rows, results all_to_all'd
back to source order. The load-bearing invariant mirrors ADR-013's:
changing the ROUTING must never change the DECISIONS — pinned here
bit-for-bit against the host-routed sliced oracle for mixed and affine
frames, across sub-window rollovers, under policy overrides and the
hierarchy cascade, on the token-bucket backend, and through the raw-id
wire lane. The overflow fallback (capacity-1 bins via bin_headroom < 1)
must re-dispatch through the host router with no admission mass lost or
duplicated, and ``--quarantine`` must be refused loudly (a collective
dispatch is one mesh-wide execution — per-slice failure domains cannot
contain it). CI runs this file in the explicit 8-virtual-device mesh
lane with zero skips allowed (ci.yml); ``make test-collective`` runs it
locally.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.core.config import HierarchySpec, MeshSpec
from ratelimiter_tpu.core.errors import InvalidConfigError
from ratelimiter_tpu.ops.route_kernels import bin_capacity
from ratelimiter_tpu.parallel.collective import CollectiveMeshLimiter
from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="collective router tests need >= 4 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

T0 = 1_700_000_000.0


def _cfg(router: str, *, algo=Algorithm.SLIDING_WINDOW, limit=10,
         devices=8, headroom=2.0, hier=None, **kw) -> Config:
    return Config(
        algorithm=algo, limit=limit, window=60.0,
        sketch=SketchParams(depth=2, width=1 << 10, sub_windows=6),
        mesh=MeshSpec(devices=devices, router=router,
                      bin_headroom=headroom),
        hierarchy=hier or HierarchySpec(),
        **kw)


def _pair(router_cfg_kw=None, **kw):
    """(host-routed oracle, collective) on identical configs/clocks."""
    ckw = dict(kw)
    ckw.update(router_cfg_kw or {})
    host = create_limiter(_cfg("host", **kw), backend="mesh",
                          clock=ManualClock(T0))
    coll = create_limiter(_cfg("collective", **ckw), backend="mesh",
                          clock=ManualClock(T0))
    assert isinstance(coll, CollectiveMeshLimiter)
    assert isinstance(host, SlicedMeshLimiter)
    assert not isinstance(host, CollectiveMeshLimiter)
    return host, coll


def _assert_equal(rh, rc, *, i=None):
    for col in ("allowed", "remaining", "retry_after", "reset_at"):
        np.testing.assert_array_equal(
            getattr(rh, col), getattr(rc, col),
            err_msg=f"{col} diverged (frame {i})")
    if rh.limits is None:
        assert rc.limits is None
    else:
        np.testing.assert_array_equal(rh.limits, rc.limits)


# ------------------------------------------------------------ parity


class TestDecisionParity:
    def test_mixed_frames_bit_identical(self):
        """Random mixed frames (every frame spans many owners, weighted
        costs, duplicate keys) — the collective all_to_all path must be
        bit-identical to the host-routed oracle, with zero overflow
        fallbacks at the default headroom."""
        host, coll = _pair()
        rng = np.random.default_rng(0)
        try:
            for i in range(12):
                b = int(rng.integers(1, 400))
                h = rng.integers(0, 1 << 64, size=b, dtype=np.uint64)
                ns = rng.integers(1, 4, size=b).astype(np.int64)
                now = T0 + i * 0.5
                _assert_equal(host.allow_hashed(h, ns, now=now),
                              coll.allow_hashed(h, ns, now=now), i=i)
            assert coll.fallbacks == 0
            assert coll.router_stats() == {
                "mode": "collective", "dispatches": 12, "fallbacks": 0,
                "fallback_reasons": {"overflow": 0, "strict": 0}}
        finally:
            host.close()
            coll.close()

    def test_affine_frames_bit_identical(self):
        """Single-owner frames (the consistent-hash-LB shape): the host
        router passes them through unsplit; the collective router still
        runs the full all_to_all step — decisions must agree anyway."""
        host, coll = _pair()
        try:
            all_ids = np.arange(1, 1 << 12, dtype=np.uint64)
            h = all_ids[host.owner_of_hash(all_ids) == 3][:64]
            assert len(h) == 64
            for i in range(4):
                now = T0 + i * 1.0
                _assert_equal(host.allow_hashed(h, now=now),
                              coll.allow_hashed(h, now=now), i=i)
            assert coll.fallbacks == 0
        finally:
            host.close()
            coll.close()

    def test_in_batch_same_key_sequencing(self):
        """A frame holding one key limit+5 times: exactly ``limit``
        admits, in FRAME ORDER — the bit-identity linchpin (the return
        route's stable compaction preserves global frame order)."""
        host, coll = _pair()
        try:
            h = np.full(15, 0xDEAD_BEEF_F00D, dtype=np.uint64)
            rh = host.allow_hashed(h, now=T0)
            rc = coll.allow_hashed(h, now=T0)
            _assert_equal(rh, rc)
            assert rc.allowed.tolist() == [True] * 10 + [False] * 5
        finally:
            host.close()
            coll.close()

    def test_rollover_parity(self):
        """Frames straddling sub-window rollovers (window 60s / 6
        sub-windows = 10s each) and a full-window expiry: the device-side
        period sync must match the host router's."""
        host, coll = _pair()
        rng = np.random.default_rng(1)
        try:
            # 15s steps cross a 10s sub-window boundary every frame;
            # the last step jumps past the full window.
            for i, dt in enumerate([0.0, 15.0, 30.0, 45.0, 61.0, 125.0]):
                b = int(rng.integers(32, 200))
                h = rng.integers(0, 1 << 64, size=b, dtype=np.uint64)
                now = T0 + dt
                _assert_equal(host.allow_hashed(h, now=now),
                              coll.allow_hashed(h, now=now), i=i)
        finally:
            host.close()
            coll.close()

    def test_token_bucket_parity(self):
        host, coll = _pair(algo=Algorithm.TOKEN_BUCKET)
        rng = np.random.default_rng(2)
        try:
            for i in range(8):
                b = int(rng.integers(1, 300))
                h = rng.integers(0, 1 << 64, size=b, dtype=np.uint64)
                now = T0 + i * 0.5
                _assert_equal(host.allow_hashed(h, now=now),
                              coll.allow_hashed(h, now=now), i=i)
        finally:
            host.close()
            coll.close()

    def test_policy_override_parity(self):
        """Per-key overrides ride the mesh-replicated policy table; the
        overridden keys' decisions AND the limits column must match."""
        host, coll = _pair()
        rng = np.random.default_rng(3)
        try:
            keys = ["vip-a", "vip-b", "cheap", "fast"]
            for key, lim in zip(keys, (2, 50, 1, 25)):
                for m in (host, coll):
                    m.set_override(key, lim)
            special = np.asarray(host._hash(keys), dtype=np.uint64)
            assert np.array_equal(special, coll._hash(keys))
            for i in range(6):
                b = int(rng.integers(64, 256))
                h = rng.integers(0, 1 << 64, size=b, dtype=np.uint64)
                h[: len(special)] = special  # overridden keys up front
                now = T0 + i * 0.5
                rh = host.allow_hashed(h, now=now)
                rc = coll.allow_hashed(h, now=now)
                _assert_equal(rh, rc, i=i)
                assert rh.limits is not None
        finally:
            host.close()
            coll.close()

    def test_hierarchy_cascade_parity(self):
        hier = HierarchySpec(tenants=4, global_limit=300)
        host, coll = _pair(hier=hier)
        rng = np.random.default_rng(4)
        try:
            for i in range(6):
                b = int(rng.integers(64, 400))
                h = rng.integers(0, 1 << 64, size=b, dtype=np.uint64)
                now = T0 + i * 0.5
                _assert_equal(host.allow_hashed(h, now=now),
                              coll.allow_hashed(h, now=now), i=i)
        finally:
            host.close()
            coll.close()

    def test_wire_lane_parity(self):
        """Raw-id premix lane with device packing requested: decisions
        and the packed wire buffers must match the host router's
        scatter-rebuilt packing."""
        host, coll = _pair()
        rng = np.random.default_rng(5)
        try:
            ids = rng.integers(0, 1 << 62, size=128, dtype=np.uint64)
            rh = host.resolve(host.launch_ids(ids, now=T0, wire=True))
            rc = coll.resolve(coll.launch_ids(ids, now=T0, wire=True))
            _assert_equal(rh, rc)
            assert rc.wire_packed is not None
            assert rh.wire_packed is not None
            pb_h, words_h, bh = rh.wire_packed
            pb_c, words_c, bc = rc.wire_packed
            assert bh == bc
            np.testing.assert_array_equal(np.asarray(pb_h),
                                          np.asarray(pb_c))
            np.testing.assert_array_equal(np.asarray(words_h),
                                          np.asarray(words_c))
        finally:
            host.close()
            coll.close()


# -------------------------------------------------- overflow fallback


class TestOverflowFallback:
    def test_capacity_one_bins_fall_back_bit_identically(self):
        """bin_headroom < 1 forces capacity-1 bins, so any frame with
        two same-owner rows on one source shard overflows. The frame
        must fall back to the host router with decisions STILL
        bit-identical — admission applied exactly once (the device step
        leaves state untouched on overflow; the fallback re-dispatches
        the original arrays)."""
        host, coll = _pair(router_cfg_kw={"headroom": 0.001})
        rng = np.random.default_rng(6)
        try:
            for i in range(6):
                b = int(rng.integers(64, 300))
                h = rng.integers(0, 1 << 64, size=b, dtype=np.uint64)
                ns = rng.integers(1, 4, size=b).astype(np.int64)
                now = T0 + i * 0.5
                _assert_equal(host.allow_hashed(h, ns, now=now),
                              coll.allow_hashed(h, ns, now=now), i=i)
            assert coll.fallbacks > 0
            assert coll.router_stats()["fallbacks"] == coll.fallbacks
        finally:
            host.close()
            coll.close()

    def test_no_lost_or_duplicated_admission_mass(self):
        """Exactly-once through the fallback, pinned on totals: a hot
        key driven to its limit through overflowing frames admits
        exactly ``limit`` units — a double-apply would admit fewer on
        later frames, a dropped frame more."""
        _, coll = _pair(router_cfg_kw={"headroom": 0.001})
        try:
            hot = np.full(4, 0xF00D, dtype=np.uint64)
            admitted = 0
            for i in range(4):
                admitted += int(coll.allow_hashed(
                    hot, now=T0 + i * 0.01).allowed.sum())
            assert admitted == 10  # limit, exactly once
            assert coll.fallbacks > 0
        finally:
            coll.close()

    def test_bin_capacity_bounds(self):
        # headroom multiplier with the binomial-tail floor...
        assert bin_capacity(1024, 8, 2.0) == 256
        # ...the tail bound dominating a thin multiplier at mid sizes
        # (mean 4, 2x-mean = 8 measured overflowing ~20% of frames)...
        assert bin_capacity(32, 8, 2.0) > 8
        # ...the flat floor on small shards, clamped to the shard...
        assert bin_capacity(8, 8, 2.0) == 8
        assert bin_capacity(4, 8, 8.0) == 4   # never above L
        # ...and headroom < 1 skipping every floor (the fallback lever).
        assert bin_capacity(64, 8, 0.001) == 1


# ---------------------------------------------- snapshot during flight


class TestSnapshotDuringInflight:
    def test_capture_quiesces_inflight_collective_dispatches(self, tmp_path):
        """save() with collective tickets un-resolved must reflect every
        LAUNCHED dispatch (quiescence by data dependence — the routed
        step commits its write-back at launch): restoring reproduces the
        post-launch counters exactly, matching the ADR-013 scatter-gather
        contract."""
        cfg = _cfg("collective", devices=4)
        coll = create_limiter(cfg, backend="mesh", clock=ManualClock(T0))
        try:
            hot = np.full(4, 0xF00D, dtype=np.uint64)
            t1 = coll.launch_ids(np.concatenate([hot, hot]), now=T0)
            t2 = coll.launch_ids(hot, now=T0)
            path = str(tmp_path / "mid.npz")
            coll.save(path)  # both windows still un-resolved
            assert coll.resolve(t1).allowed.tolist() == [True] * 8
            assert coll.resolve(t2).allowed.tolist() == [True, True,
                                                         False, False]
            restored = create_limiter(cfg, backend="mesh",
                                      clock=ManualClock(T0))
            try:
                restored.restore(path)
                # 12 units offered pre-snapshot, limit 10: nothing left.
                assert restored.allow_ids(
                    hot, now=T0).allowed.tolist() == [False] * 4
            finally:
                restored.close()
        finally:
            coll.close()

    def test_restore_round_trip_parity(self, tmp_path):
        """Snapshot taken by the collective mesh restores into a fresh
        collective mesh with decisions matching the host-routed oracle
        restored from ITS own snapshot of the same history."""
        host, coll = _pair()
        rng = np.random.default_rng(7)
        h = rng.integers(0, 1 << 64, size=200, dtype=np.uint64)
        try:
            host.allow_hashed(h, now=T0)
            coll.allow_hashed(h, now=T0)
            ph = str(tmp_path / "host.npz")
            pc = str(tmp_path / "coll.npz")
            host.save(ph)
            coll.save(pc)
            host2 = create_limiter(_cfg("host"), backend="mesh",
                                   clock=ManualClock(T0))
            coll2 = create_limiter(_cfg("collective"), backend="mesh",
                                   clock=ManualClock(T0))
            try:
                host2.restore(ph)
                coll2.restore(pc)
                _assert_equal(host2.allow_hashed(h, now=T0 + 1.0),
                              coll2.allow_hashed(h, now=T0 + 1.0))
            finally:
                host2.close()
                coll2.close()
        finally:
            host.close()
            coll.close()


# ----------------------------------------------------- config refusal


class TestQuarantineRefusal:
    def test_config_refuses_collective_plus_quarantine(self):
        with pytest.raises(InvalidConfigError, match="blast radius"):
            create_limiter(
                Config(algorithm=Algorithm.SLIDING_WINDOW, limit=10,
                       window=60.0,
                       sketch=SketchParams(depth=2, width=1 << 10),
                       mesh=MeshSpec(devices=4, router="collective",
                                     quarantine=True)),
                backend="mesh", clock=ManualClock(T0))

    def test_config_refuses_unknown_router(self):
        with pytest.raises(InvalidConfigError, match="router"):
            create_limiter(
                Config(algorithm=Algorithm.SLIDING_WINDOW, limit=10,
                       window=60.0,
                       sketch=SketchParams(depth=2, width=1 << 10),
                       mesh=MeshSpec(devices=4, router="p2p")),
                backend="mesh", clock=ManualClock(T0))

    def test_cli_refuses_collective_plus_quarantine(self):
        """The serving binary's loud SystemExit — refused at argument
        validation, before any device work."""
        import os
        import subprocess
        import sys

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "mesh", "--router", "collective",
             "--quarantine", "--port", "1"],
            capture_output=True, text=True, timeout=120, env=env)
        assert out.returncode != 0
        assert "blast radius" in (out.stderr + out.stdout)
