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
wire lane. The launch's ONE staged operand (PR 45: a pooled ``uint64[n,
2L + 2]`` slot, a row a device, placed by one sharded device_put) is
held to the same oracle — replies and every state leaf — over rule x
lane x pad, and its pool to the one-chip lane's ownership rule: a slot
belongs to its ticket until resolve, on every exit. The overflow
fallback (capacity-1 bins via bin_headroom < 1) must re-dispatch
through the host router with no admission mass lost or duplicated,
and ``--quarantine`` must be refused loudly (a collective dispatch is
one mesh-wide execution — per-slice failure domains cannot
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
                "mode": "collective", "dispatches": 12, "placements": 96,
                "fallbacks": 0,
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


# ------------------------------------------------- the staged operand


RULES = {"windowed": Algorithm.SLIDING_WINDOW,
         "bucket": Algorithm.TOKEN_BUCKET}
N = 4  # the deployment's slice count (mesh4-c3-coll)


def _decide(lim, premix, h, ns, now):
    return (lim.allow_ids if premix else lim.allow_hashed)(h, ns, now=now)


def _assert_state_equal(host, coll, touched=range(N)):
    """Every state leaf of the slices a frame ``touched``. (A slice that
    owns no row of a frame is not dispatched to by the host router; the
    routed step runs on every slice, over zero rows: its period and
    refill bookkeeping advance there one frame earlier.)"""
    for i in touched:
        sh, sc = host.slices[i], coll.slices[i]
        assert set(sh._state) == set(sc._state)
        for k in sh._state:
            np.testing.assert_array_equal(
                np.asarray(sh._state[k]), np.asarray(sc._state[k]),
                err_msg=f"state leaf {k} of slice {i} diverged")


@pytest.mark.parametrize("L", [8, 16, 64])
@pytest.mark.parametrize("premix", [False, True],
                         ids=["hashed", "premix"])
@pytest.mark.parametrize("rule", list(RULES))
class TestStagedFrame:
    def test_replies_and_every_state_leaf_equal_the_host_routers(
            self, rule, premix, L):
        """Frames of one pad bucket, the fullest first, so that every
        later frame is staged into a recycled slot that still holds a
        longer frame's rows: b = n*L (no pad row), a partial last row,
        the bucket's smallest frame (whole rows of padding), b = n*L
        again, and a frame with fewer rows than slices."""
        host, coll = _pair(algo=RULES[rule], devices=N)
        rng = np.random.default_rng(L)
        owner_of = host.owner_of_id if premix else host.owner_of_hash
        try:
            for i, b in enumerate([N * L, N * L - 5, N * (L // 2) + 1,
                                   N * L, 3]):
                h = rng.integers(0, 1 << 62, size=b, dtype=np.uint64)
                h[-min(6, b - 1):] = h[0]  # one key many times: sequencing
                ns = rng.integers(1, 4, size=b).astype(np.int64)
                now = T0 + i * 0.5
                _assert_equal(_decide(host, premix, h, ns, now),
                              _decide(coll, premix, h, ns, now), i=i)
                touched = np.unique(owner_of(h)).tolist()
                assert i or len(touched) == N
                _assert_state_equal(host, coll, touched)
            assert coll.fallbacks == 0 and coll.dispatches == 5
            # Two buckets were used (L, and 8 for the three-row frame),
            # and every launch of a bucket took the one slot back.
            assert {k: len(v) for k, v in coll._slots.items()} \
                == dict.fromkeys({L, 8}, 1)
        finally:
            host.close()
            coll.close()

    def test_a_launch_places_one_shard_a_device_and_no_host_scalar(
            self, rule, premix, L, monkeypatch):
        """``placements`` rises by n_slices a launch, and every operand
        the jitted call sees is a committed device array on the mesh —
        no NumPy or Python scalar for ``jit`` to replicate per call."""
        from ratelimiter_tpu.ops import route_kernels

        seen = []
        real = route_kernels.build_routed_step

        def spying(*args, **kw):
            step = real(*args, **kw)

            def call(*operands):
                seen.append(jax.tree_util.tree_leaves(operands))
                return step(*operands)
            return call

        monkeypatch.setattr(route_kernels, "build_routed_step", spying)
        coll = create_limiter(_cfg("collective", algo=RULES[rule],
                                   devices=N),
                              backend="mesh", clock=ManualClock(T0))
        try:
            devices = set(coll.mesh.devices.flat)
            for i, b in enumerate([N * L, N * L - 5]):
                h = np.arange(1, b + 1, dtype=np.uint64)
                before = coll.placements
                _decide(coll, premix, h, None, T0 + i)
                assert coll.placements - before == N
                assert coll.router_stats()["placements"] == coll.placements
            assert len(seen) == 2
            for leaves in seen:
                assert leaves and all(
                    isinstance(x, jax.Array) and x.committed
                    and set(x.sharding.device_set) == devices
                    for x in leaves), [type(x) for x in leaves]
                staged = [x for x in leaves if x.dtype == np.uint64]
                assert [x.shape for x in staged] == [(N, 2 * L + 2)]
                assert [sh.data.shape for sh in
                        staged[0].addressable_shards] == [(1, 2 * L + 2)] * N
        finally:
            coll.close()


class _NeverReady:
    """A launched ticket's result whose wait fails at resolve."""

    def block_until_ready(self):
        raise RuntimeError("device lost")


@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
class TestSlotOwnership:
    """A slot is the ticket's from launch to resolve and the pool's
    otherwise (HashedLane's rule): never handed out while its ticket is
    open, always handed out again after — whichever way the ticket
    ended."""

    def _launch(self, coll, premix, h, now=T0):
        return (coll.launch_ids if premix else coll.launch_hashed)(
            h, now=now)

    def test_not_before_the_ticket_resolves_and_again_after(self, premix):
        host, coll = _pair(devices=N)
        try:
            h = np.arange(1, 30, dtype=np.uint64)
            t1 = self._launch(coll, premix, h)
            t2 = self._launch(coll, premix, h)
            first, second = t1.slot, t2.slot
            assert first is not None and second is not None
            assert first is not second and first.shape == (N, 2 * 8 + 2)
            assert not coll._slots.get(8)
            r1 = coll.resolve(t1)
            assert t1.slot is None and coll._slots[8] == [first]
            assert coll.resolve(t1) is r1  # and gives nothing back twice
            assert coll._slots[8] == [first]
            t3 = self._launch(coll, premix, h)
            assert t3.slot is first
            r2, r3 = coll.resolve(t2), coll.resolve(t3)
            assert sorted(map(id, coll._slots[8])) == sorted(
                map(id, (first, second)))
            for rc in (r1, r2, r3):  # decided in launch order
                _assert_equal(_decide(host, premix, h, None, T0), rc)
        finally:
            host.close()
            coll.close()

    def test_again_after_an_overflow_fallback(self, premix):
        """The fallback re-dispatches the ORIGINAL frame (ticket.arrays),
        never the slot: its replies are the host router's, and the slot
        is back in the pool before the host router runs."""
        host, coll = _pair(router_cfg_kw={"headroom": 0.001}, devices=N)
        try:
            h = np.full(24, 0xF00D, dtype=np.uint64)
            t = self._launch(coll, premix, h)
            slot = t.slot
            slot_ids = slot[:, :8].copy()
            res = coll.resolve(t)
            assert coll.fallbacks == 1 and t.slot is None
            _assert_equal(_decide(host, premix, h, None, T0), res)
            _assert_state_equal(host, coll, np.unique(
                (host.owner_of_id if premix else host.owner_of_hash)(h)))
            np.testing.assert_array_equal(slot[:, :8], slot_ids)
            assert self._launch(coll, premix, h, now=T0 + 1).slot is slot
        finally:
            host.close()
            coll.close()

    @pytest.mark.parametrize("fail_open", [True, False],
                             ids=["fail-open", "fail-closed"])
    def test_again_after_a_failed_launch(self, premix, fail_open):
        """An injected failure is raised under the locks, after the
        frame was staged and placed: fail-open answers the frame by
        policy, fail-closed raises — the slot is back either way."""
        from ratelimiter_tpu.core.errors import StorageUnavailableError

        coll = create_limiter(_cfg("collective", devices=N,
                                   fail_open=fail_open),
                              backend="mesh", clock=ManualClock(T0))
        try:
            h = np.arange(1, 30, dtype=np.uint64)
            slot = self._launch(coll, premix, h).slot  # left open on purpose
            coll.slices[2].inject_failure()
            if fail_open:
                t = self._launch(coll, premix, h)
                assert t.slot is None and t.result.allowed.all()
            else:
                with pytest.raises(StorageUnavailableError):
                    self._launch(coll, premix, h)
            (spare,) = coll._slots[8]
            assert spare is not slot
            assert coll.dispatches == 1 and coll.placements == N
            coll.slices[2].heal()
            assert self._launch(coll, premix, h).slot is spare
        finally:
            coll.close()

    def test_again_after_a_result_that_never_came(self, premix):
        coll = create_limiter(_cfg("collective", devices=N, fail_open=True),
                              backend="mesh", clock=ManualClock(T0))
        try:
            h = np.arange(1, 30, dtype=np.uint64)
            t = self._launch(coll, premix, h)
            slot = t.slot
            t.outs = _NeverReady()
            assert coll.resolve(t).allowed.all()  # answered by policy
            assert t.slot is None and coll._slots[8] == [slot]
            assert self._launch(coll, premix, h).slot is slot
        finally:
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
