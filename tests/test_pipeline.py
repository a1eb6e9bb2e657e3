"""Pipelined serving hot path (ADR-010): launch/resolve dispatch API.

The load-bearing invariant: sequential per-key semantics SURVIVE overlap.
With up to N dispatches in flight, every decision must equal what the old
launch→block→serialize path would have produced — state threading via
donated buffers (each launch consumes the previous launch's state) is
what carries the ordering, and these tests pin it against the
single-dispatch oracle decision-for-decision. Plus: snapshots taken while
dispatches are in flight must capture a consistent (fully applied) state,
the staging-buffer pool must actually recycle, and the pipelined path
must not be slower than the synchronous one on the CPU harness (the
pinned smoke CI runs).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    StorageUnavailableError,
    create_limiter,
)
from ratelimiter_tpu.observability import MetricsDecorator, Registry
from ratelimiter_tpu.serving import MicroBatcher

T0 = 1_700_000_000.0


#: The backends built on the hashed, pipelined lane (algorithms/
#: hashed_lane.py): every test that holds the lane itself runs on both.
LANE_BACKENDS = ("sketch", "dense")
on_both = pytest.mark.parametrize("backend", LANE_BACKENDS)


def _mk(limit=5, algo=Algorithm.SLIDING_WINDOW, backend="sketch", **kw):
    cfg = Config(algorithm=algo, limit=limit, window=60.0,
                 sketch=SketchParams(depth=3, width=512, sub_windows=6),
                 **kw)
    return create_limiter(cfg, backend=backend, clock=ManualClock(T0))


# ------------------------------------------------------ limiter-level API

class TestLaunchResolve:
    @on_both
    def test_interleaved_same_key_matches_single_dispatch_oracle(
            self, backend):
        """K batches of the same hot key launched back to back WITHOUT
        resolving in between must decide exactly like the synchronous
        path: the 6th unit request on a limit-5 key is denied no matter
        which in-flight window it rode in."""
        lim = _mk(limit=5, backend=backend)
        oracle = _mk(limit=5, backend=backend)
        frames = [["hot", "hot"], ["hot", "cold"], ["hot", "hot"],
                  ["cold", "hot"]]
        tickets = [lim.launch_batch(f) for f in frames]     # all in flight
        piped = [lim.resolve(t).allowed.tolist() for t in tickets]
        want = [oracle.allow_batch(f).allowed.tolist() for f in frames]
        assert piped == want
        lim.close()
        oracle.close()

    @on_both
    def test_resolve_order_does_not_matter(self, backend):
        """Resolving newest-first returns the same per-ticket decisions:
        ordering lives in the device-side state chain, not in the resolve
        calls."""
        lim = _mk(limit=3, backend=backend)
        oracle = _mk(limit=3, backend=backend)
        frames = [["k"], ["k"], ["k"], ["k"], ["k"]]
        tickets = [lim.launch_batch(f) for f in frames]
        for t in reversed(tickets):
            lim.resolve(t)
        got = [bool(t.result.allowed[0]) for t in tickets]
        want = [bool(oracle.allow_batch(f).allowed[0]) for f in frames]
        assert got == want == [True, True, True, False, False]
        lim.close()
        oracle.close()

    @on_both
    def test_resolve_is_idempotent(self, backend):
        lim = _mk(backend=backend)
        t = lim.launch_batch(["a"])
        first = lim.resolve(t)
        assert lim.resolve(t) is first
        lim.close()

    @on_both
    def test_token_bucket_pipelined_matches_oracle(self, backend):
        lim = _mk(limit=4, algo=Algorithm.TOKEN_BUCKET, backend=backend)
        oracle = _mk(limit=4, algo=Algorithm.TOKEN_BUCKET, backend=backend)
        frames = [["k", "k"], ["k", "k"], ["k"]]
        tickets = [lim.launch_batch(f) for f in frames]
        got = [lim.resolve(t).allowed.tolist() for t in tickets]
        want = [oracle.allow_batch(f).allowed.tolist() for f in frames]
        assert got == want
        # Device-computed retry matches too (the step's own finish).
        t_deny = lim.launch_batch(["k"])
        o_deny = oracle.allow_batch(["k"])
        r = lim.resolve(t_deny)
        assert r.retry_after[0] == pytest.approx(o_deny.retry_after[0])
        assert r.reset_at[0] == pytest.approx(o_deny.reset_at[0])
        lim.close()
        oracle.close()

    def test_device_side_retry_reset_match_legacy_values(self):
        """The step computes retry/reset on the device (at its own end,
        ADR-010 addendum); the values must equal the host formulas:
        retry = time to window reset for denied, 0 for allowed."""
        lim = _mk(limit=2)
        out = lim.resolve(lim.launch_batch(["x", "x", "x"]))
        assert out.allowed.tolist() == [True, True, False]
        assert out.retry_after[0] == 0.0 and out.retry_after[1] == 0.0
        assert out.retry_after[2] == pytest.approx(60.0 - (T0 % 60.0))
        assert np.all(out.reset_at == out.reset_at[0])
        assert out.remaining.dtype == np.int64
        lim.close()

    @on_both
    def test_staging_buffers_recycle(self, backend):
        """Launch→resolve→launch at one batch shape reuses the SAME
        staging buffer (the per-dispatch np.zeros allocations are gone);
        overlapping launches get distinct buffers. A slot is one uint64
        buffer [ids(P) | n(P) | now_us(1)]."""
        lim = _mk(limit=1000, backend=backend)
        t1 = lim.launch_batch(["a", "b"])
        assert t1.slot.dtype == np.uint64
        assert t1.slot.shape == (2 * t1.padded + 1,)
        first = t1.slot
        t2 = lim.launch_batch(["c", "d"])       # in flight with t1
        second = t2.slot
        assert second is not first
        assert not np.shares_memory(first, second)
        lim.resolve(t1)
        lim.resolve(t2)
        t3 = lim.launch_batch(["e", "f"])       # recycled from the pool
        assert t3.slot is first or t3.slot is second
        lim.resolve(t3)
        lim.close()

    @on_both
    def test_launch_fail_open_and_fail_closed(self, backend):
        lim = _mk(limit=5, fail_open=True, backend=backend)
        lim.resolve(lim.launch_batch(["warm", "up"]))   # seed the pool
        pool = sum(len(v) for v in lim._staging.values())
        lim.inject_failure()
        for _ in range(3):
            t = lim.launch_batch(["x", "y"])
            out = lim.resolve(t)
            assert out.fail_open and out.allowed.all()
        # Failed launches must return their staging slot to the pool —
        # a leak here re-introduces the per-dispatch allocations under
        # exactly the failure windows fail-open exists for.
        assert sum(len(v) for v in lim._staging.values()) == pool
        assert lim._inflight_mass == 0
        lim.heal()
        lim.close()

        lim2 = _mk(limit=5, fail_open=False, backend=backend)
        lim2.inject_failure()
        with pytest.raises(StorageUnavailableError):
            lim2.launch_batch(["x"])
        lim2.close()

    def test_exact_backend_pre_resolves(self):
        """Backends without an async device path answer at launch via the
        base fallback, so callers can use one API everywhere."""
        lim, _ = ( _mk(limit=2, backend="exact"), None)
        assert lim.pipelined is False
        t = lim.launch_batch(["k", "k", "k"])
        assert t.resolved
        assert lim.resolve(t).allowed.tolist() == [True, True, False]
        lim.close()

    def test_decorated_limiter_routes_launch_to_backend(self):
        """A decorator stack must delegate launch_batch to the backend's
        real pipelined path (not the base eager fallback) and observe the
        batch once, at resolve."""
        reg = Registry()
        lim = MetricsDecorator(_mk(limit=3), registry=reg)
        assert lim.pipelined is True
        t = lim.launch_batch(["k", "k", "k", "k"])
        assert not t.resolved                    # genuinely deferred
        out = lim.resolve(t)
        assert out.allowed.tolist() == [True, True, True, False]
        assert reg.get("rate_limiter_requests_total").value(
            algorithm="sliding_window", result="mixed") == 4.0
        lim.close()


    def test_strict_overload_gate_counts_inflight_mass(self):
        """overload_policy='strict' must not be dilutable by the
        pipeline: launched-but-unresolved mass counts against the
        accuracy budget at full offered weight, so a deep in-flight
        window cannot slip inflight*max_batch admissions past the gate
        before any resolve lands."""
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5,
                     window=60.0,
                     sketch=SketchParams(depth=3, width=256, sub_windows=6,
                                         overload_policy="strict"))
        lim = create_limiter(cfg, backend="sketch",
                             clock=ManualClock(T0))
        budget = lim.mass_budget            # 2560 at this geometry
        n = budget // 2 + 1
        t1 = lim.launch_batch([f"a{i}" for i in range(n)])
        t2 = lim.launch_batch([f"b{i}" for i in range(n)])
        # Neither resolved yet: in-flight offered mass 2n > budget, so
        # the NEXT launch must deny-all without dispatching.
        t3 = lim.launch_batch(["c"])
        assert t3.resolved and not t3.result.allowed.any()
        assert lim.overload_periods >= 1
        # The legitimately launched work still resolves normally.
        assert lim.resolve(t1).allowed.all()
        assert lim.resolve(t2).allowed.all()
        # In-flight pessimism fully replaced by confirmed mass (no leak).
        assert lim._inflight_mass == 0
        assert lim.in_window_admitted_mass() == 2 * n
        lim.close()


# ------------------------------------------------------- snapshot quiesce

class TestSnapshotDuringInflight:
    @on_both
    def test_capture_waits_for_inflight_launches(self, backend, tmp_path):
        """capture_state while dispatches are in flight must quiesce the
        pipeline: the data dependence on the donated state chain means
        the captured arrays reflect EVERY launched step. Restoring the
        snapshot into a fresh limiter reproduces the post-launch
        counters exactly."""
        lim = _mk(limit=10, backend=backend)
        t1 = lim.launch_batch(["hot"] * 4)
        t2 = lim.launch_batch(["hot"] * 4)
        path = str(tmp_path / "mid.npz")
        lim.save(path)                       # capture with both in flight
        # The tickets still resolve correctly after the capture.
        assert lim.resolve(t1).allowed.tolist() == [True] * 4
        assert lim.resolve(t2).allowed.tolist() == [True] * 4

        restored = _mk(limit=10, backend=backend)
        restored.restore(path)
        # 8 units consumed in the snapshot: exactly 2 admits left.
        out = restored.allow_batch(["hot"] * 4)
        assert out.allowed.tolist() == [True, True, False, False]
        lim.close()
        restored.close()


# --------------------------------------------------- pipelined MicroBatcher

def _run(coro):
    return asyncio.run(coro)


class TestPipelinedBatcher:
    @on_both
    def test_interleaved_frames_match_oracle(self, backend):
        """Same-key frames submitted through the pipelined micro-batcher
        (inflight=4) decide exactly like sequential single dispatches on
        a fresh limiter — coalescing and overlap change the batching, not
        the decisions."""
        lim = _mk(limit=7, backend=backend)
        oracle = _mk(limit=7, backend=backend)
        frames = [["hot", "a", "hot"], ["hot", "hot"], ["b", "hot"],
                  ["hot", "hot", "hot"]]

        async def drive():
            b = MicroBatcher(lim, max_batch=4096, max_delay=1e-3,
                             inflight=4, registry=Registry())
            assert b._pipelined
            futs = []
            for f in frames:
                futs.extend(b.submit_many_nowait((k, 1) for k in f))
            res = await asyncio.gather(*futs)
            await b.drain()
            b.close()
            return [r.allowed for r in res]

        got = _run(drive())
        want = [r.allowed
                for f in frames for r in oracle.allow_batch(f).results()]
        assert got == want
        lim.close()
        oracle.close()

    def test_inflight_gauge_and_phase_histograms(self):
        reg = Registry()
        lim = _mk(limit=100000)

        async def drive():
            b = MicroBatcher(lim, max_batch=64, max_delay=1e-4,
                             inflight=4, registry=reg)
            futs = [b.submit_nowait(f"k{i}") for i in range(256)]
            await asyncio.gather(*futs)
            await b.drain()
            b.close()

        _run(drive())
        assert reg.get("rate_limiter_pipeline_launch_seconds").count() >= 4
        assert reg.get("rate_limiter_pipeline_resolve_seconds").count() >= 4
        # Every launch resolved: the gauge is back to zero.
        assert reg.get("rate_limiter_pipeline_inflight").value() == 0.0
        lim.close()

    def test_non_pipelined_backend_uses_legacy_path(self):
        lim, _ = _mk(limit=3, backend="exact"), None

        async def drive():
            b = MicroBatcher(lim, max_batch=16, max_delay=1e-4,
                             inflight=8, registry=Registry())
            assert not b._pipelined
            out = await asyncio.gather(*[b.submit_nowait("k")
                                         for _ in range(5)])
            await b.drain()
            b.close()
            return [r.allowed for r in out]

        assert _run(drive()) == [True, True, True, False, False]
        lim.close()

    def test_slo_disables_pipelining(self):
        """Pipelining and the dispatch SLO are mutually exclusive (same
        rule as the native door): a launch blocked on a full window sits
        outside any wait_for, so its waiters could hang past the SLO."""
        lim = _mk(limit=10)
        b = MicroBatcher(lim, dispatch_timeout=0.5, inflight=8,
                         registry=Registry())
        assert not b._pipelined
        b.close()
        lim.close()

    def test_adaptive_rearm_triggers_on_mark_crossing(self):
        """Batch frames jump the queue depth by whole frames; the
        adaptive re-arm must fire on threshold CROSSINGS, not exact
        matches (a 20-deep frame hops straight over the depth-8 and
        depth-16 marks)."""
        lim = _mk(limit=100000)

        async def drive():
            b = MicroBatcher(lim, max_batch=64, max_delay=50e-3,
                             inflight=4, registry=Registry())
            assert b._adaptive_marks == [8, 16, 32, 48]
            futs = b.submit_many_nowait((f"k{i}", 1) for i in range(4))
            assert b._armed_depth == 4            # initial arm
            futs += b.submit_many_nowait((f"j{i}", 1) for i in range(20))
            # The second frame jumped the depth 4 -> 24, CROSSING the 8
            # and 16 marks without ever equalling one: the timer must
            # have been re-armed (armed_depth tracked the crossing).
            assert b._armed_depth == 24
            res = await asyncio.gather(*futs)
            await b.drain()
            b.close()
            return res

        assert all(r.allowed for r in _run(drive()))
        lim.close()

    def test_adaptive_delay_keeps_decisions_exact(self):
        """The queue-depth-aware timer re-arm must not drop or duplicate
        a request: N submissions crossing several adaptive marks all
        resolve, and a limit-L key admits exactly L."""
        lim = _mk(limit=50)

        async def drive():
            b = MicroBatcher(lim, max_batch=64, max_delay=5e-3,
                             inflight=4, adaptive_delay=True,
                             registry=Registry())
            futs = [b.submit_nowait("hot") for _ in range(120)]
            res = await asyncio.gather(*futs)
            await b.drain()
            b.close()
            return res

        res = _run(drive())
        assert len(res) == 120 and sum(r.allowed for r in res) == 50
        lim.close()


# ----------------------------------------------------- pinned smoke (CI)

class TestPipelineSmoke:
    def test_pipelined_not_slower_than_sync_on_cpu(self):
        """Pinned throughput smoke: the pipelined launch/resolve path
        (window 8) must not be slower than the synchronous path on the
        CPU harness. The margin absorbs scheduler noise on shared CI
        boxes — the claim guarded is 'pipelining is free when overlap
        buys nothing', not a speedup."""
        from ratelimiter_tpu.ops.hashing import splitmix64

        lim = _mk(limit=1 << 20)
        rng = np.random.default_rng(0)
        h = splitmix64(rng.integers(1, 1 << 40, size=512, dtype=np.uint64))
        reps = 60
        lim.allow_hashed(h, now=T0)                      # compile

        t0 = time.perf_counter()
        for i in range(reps):
            lim.allow_hashed(h, now=T0 + i * 1e-3)
        sync_s = time.perf_counter() - t0

        window: list = []
        t0 = time.perf_counter()
        for i in range(reps):
            if len(window) >= 8:
                lim.resolve(window.pop(0))
            window.append(lim.launch_hashed(h, now=T0 + (reps + i) * 1e-3))
        while window:
            lim.resolve(window.pop(0))
        piped_s = time.perf_counter() - t0

        assert piped_s <= sync_s * 1.5, (
            f"pipelined path regressed: {piped_s:.4f}s vs sync "
            f"{sync_s:.4f}s over {reps} dispatches")
        lim.close()
