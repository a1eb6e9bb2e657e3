"""Cross-slice scatter-gather scheduler tests (ISSUE-6 acceptance, ADR-013).

Mixed frames — frames whose keys span several device slices — used to
fork-join across every device queue and collapsed under load. The
scheduler fixes that with (1) ragged per-device
sub-framing with ONE completion barrier per frame, (2) cross-slice
launch coalescing (many clients' frames merge into one padded dispatch
per device per batching window, never overshooting the largest
prewarmed pad shape), and (3) completion batching + extended BatchJoin
reassembly in the native door. The load-bearing invariant is unchanged
from ADR-012: coalescing changes the BATCHING, never the DECISIONS —
pinned here bit-for-bit against single-device oracles per key lane,
along with snapshot-during-coalesce quiescence, fail-open OR-folding
over reassembled frames, the debt-slab visibility surface riding the
same mesh lane, and a pinned coalescer-not-slower CPU smoke. CI runs
this file in the explicit 8-virtual-device mesh lane with zero skips
allowed (ci.yml).
"""

from __future__ import annotations

import asyncio
import time

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
from ratelimiter_tpu.algorithms.sketch import SketchLimiter
from ratelimiter_tpu.observability import MetricsDecorator, Registry
from ratelimiter_tpu.parallel import SlicedMeshLimiter
from ratelimiter_tpu.serving import MicroBatcher

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 (virtual) devices")

T0 = 1_700_000_000.0


def _cfg(**kw):
    base = dict(
        algorithm=Algorithm.SLIDING_WINDOW,
        limit=10,
        window=60.0,
        sketch=SketchParams(depth=2, width=1 << 10, sub_windows=6),
    )
    base.update(kw)
    return Config(**base)


def _run(coro):
    return asyncio.run(coro)


def _coalesce(lim, frames, *, max_batch=1 << 15):
    """Drive one coalescing window through the MicroBatcher: every frame
    submitted in the same loop tick lands in one window (max_delay gives
    the timer no chance to fire in between) and the batcher answers each
    from its row range of the single window dispatch."""
    async def drive():
        b = MicroBatcher(lim, max_batch=max_batch, max_delay=5e-3,
                         inflight=4, registry=Registry())
        futs = [b.submit_hashed_nowait(ids, ns) for ids, ns in frames]
        out = await asyncio.gather(*futs)
        await b.drain()
        b.close()
        return out

    return _run(drive())


# ------------------------------------------------------- ordering oracle


class TestCoalescedOrderingOracle:
    def test_mixed_frames_bit_identical_to_per_slice_oracle(self):
        """Several clients' MIXED frames coalesced into one window must
        decide exactly like single-device limiters fed each slice's ids
        in arrival order — the acceptance wording verbatim: coalescing
        merges dispatches, the per-key decision stream is untouched
        (allowed, remaining, retry_after, reset_at — all bit-identical).
        """
        cfg = _cfg(limit=5)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        rng = np.random.default_rng(7)
        frames = []
        for _ in range(6):
            ids = rng.integers(1, 1 << 40, size=96, dtype=np.uint64)
            ns = np.ones(96, dtype=np.int64)
            frames.append((ids, ns))
        outs = _coalesce(mesh, frames)
        assert all(len(o) == 96 for o in outs)

        # Oracle: the window in arrival order, partitioned by owner.
        window_ids = np.concatenate([f[0] for f in frames])
        owners = mesh.owner_of_id(window_ids)
        allowed = np.concatenate([o.allowed for o in outs])
        remaining = np.concatenate([o.remaining for o in outs])
        retry = np.concatenate([o.retry_after for o in outs])
        reset = np.concatenate([o.reset_at for o in outs])
        for dev in range(4):
            idx = np.flatnonzero(owners == dev)
            if not idx.size:
                continue
            oracle = SketchLimiter(cfg, ManualClock(T0))
            ref = oracle.allow_ids(window_ids[idx])
            np.testing.assert_array_equal(allowed[idx], ref.allowed)
            np.testing.assert_array_equal(remaining[idx], ref.remaining)
            np.testing.assert_array_equal(retry[idx], ref.retry_after)
            np.testing.assert_array_equal(reset[idx], ref.reset_at)
            oracle.close()
        mesh.close()

    def test_interleaved_same_key_across_coalesced_frames(self):
        """A hot id recurring across the window's frames is sequenced in
        ARRIVAL order: exactly `limit` admits, and they are the FIRST
        `limit` occurrences counted across frame boundaries — in-window
        segment ordering decides duplicates exactly as sequential
        per-frame dispatches would (ADR-013)."""
        cfg = _cfg(limit=7)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        hot = np.uint64(0xBEEF)
        rng = np.random.default_rng(13)
        frames = []
        for _ in range(5):
            ids = rng.integers(1, 1 << 40, size=32, dtype=np.uint64)
            ids[0::8] = hot  # 4 occurrences per frame, 20 in the window
            frames.append((ids, np.ones(32, dtype=np.int64)))
        outs = _coalesce(mesh, frames)
        hot_decisions = np.concatenate(
            [o.allowed[f[0] == hot] for o, f in zip(outs, frames)])
        assert hot_decisions.sum() == 7
        assert bool(np.all(hot_decisions[:7]))
        assert not bool(np.any(hot_decisions[7:]))
        mesh.close()

    def test_row_view_slices_are_views_with_wire_offsets(self):
        """BatchResult.rows hands back numpy VIEWS over the window result
        (no copies on the scatter-back path) and re-bases the packed wire
        buffers by row offset so the encoder can frame the sub-range from
        the same packed words buffer."""
        mesh = SlicedMeshLimiter(_cfg(), ManualClock(T0), n_devices=4)
        ids = np.arange(1, 257, dtype=np.uint64)
        res = mesh.resolve(mesh.launch_ids(ids, wire=True))
        assert res.wire_packed is not None
        win = res.rows(64, 128)
        assert win.remaining.base is not None  # a view, not a copy
        np.testing.assert_array_equal(win.allowed, res.allowed[64:192])
        bits, words, padded, off = win.wire_packed
        assert off == 64 and words is res.wire_packed[1]
        # And a nested slice accumulates the offset.
        sub = win.rows(8, 16)
        assert sub.wire_packed[3] == 72
        np.testing.assert_array_equal(sub.allowed, res.allowed[72:88])
        mesh.close()

    def test_coalescer_never_dispatches_past_largest_prewarmed_pad(self):
        """A window concatenation must never exceed 2*max_batch — the
        largest pad shape _prewarm compiles (the lone-oversized-frame
        allowance). An oversized frame arriving over a non-empty window
        flushes the window FIRST and then dispatches alone; otherwise
        coalescing would pad past every prewarmed shape and land an XLA
        compile on the hot path — the exact r06 collapse mode ADR-013
        exists to prevent. Arrival-order sequencing must survive the
        early flush (the two dispatches run FIFO on the launch
        executor), pinned against the per-slice oracle."""
        cfg = _cfg(limit=5)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        max_batch = 64
        rng = np.random.default_rng(23)
        hot = np.uint64(0xF00D)
        small = rng.integers(1, 1 << 40, size=40, dtype=np.uint64)
        big = rng.integers(1, 1 << 40, size=100, dtype=np.uint64)
        small[:4] = hot
        big[:4] = hot  # duplicates straddle the flush boundary

        async def drive():
            b = MicroBatcher(mesh, max_batch=max_batch, max_delay=5e-3,
                             inflight=4, registry=Registry())
            sizes = []
            orig = b._dispatch_hashed

            async def spy(ids, ns, fut, trace_id=0):
                sizes.append(int(ids.shape[0]))
                await orig(ids, ns, fut, trace_id)

            b._dispatch_hashed = spy
            futs = [b.submit_hashed_nowait(
                        ids, np.ones(ids.shape[0], dtype=np.int64))
                    for ids in (small, big)]
            outs = await asyncio.gather(*futs)
            await b.drain()
            b.close()
            return outs, sizes

        outs, sizes = _run(drive())
        assert sizes == [40, 100]  # flushed apart, neither concatenated
        assert max(sizes) <= 2 * max_batch
        # Decisions still sequence in arrival order across the flush.
        window_ids = np.concatenate([small, big])
        owners = mesh.owner_of_id(window_ids)
        allowed = np.concatenate([o.allowed for o in outs])
        for dev in range(4):
            idx = np.flatnonzero(owners == dev)
            if not idx.size:
                continue
            oracle = SketchLimiter(cfg, ManualClock(T0))
            ref = oracle.allow_ids(window_ids[idx])
            np.testing.assert_array_equal(allowed[idx], ref.allowed)
            oracle.close()
        hot_decisions = allowed[window_ids == hot]
        assert hot_decisions.sum() == 5 and bool(np.all(hot_decisions[:5]))
        mesh.close()

    def test_lone_oversized_frame_carved_into_prewarmed_segments(self):
        """A SINGLE hashed frame larger than 2*max_batch (the wire
        protocol admits up to ~87K ids regardless of --max-batch) must
        not dispatch whole — it would pad past every prewarmed shape
        and pay the XLA compile on the hot path. The asyncio door
        mirrors the native dispatcher's carve: max_batch segments
        dispatched in order through the FIFO executors, reassembled
        host-side. Decisions stay bit-identical to the per-slice oracle
        fed the frame in order (same-key sequencing crosses segment
        boundaries), and the merged result still encodes as one
        RESULT_HASHED frame via the packbits path."""
        cfg = _cfg(limit=5)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        max_batch = 64
        rng = np.random.default_rng(29)
        hot = np.uint64(0xCAFE)
        big = rng.integers(1, 1 << 40, size=300, dtype=np.uint64)
        big[0::30] = hot  # 10 occurrences, straddling segment cuts

        async def drive():
            b = MicroBatcher(mesh, max_batch=max_batch, max_delay=5e-3,
                             inflight=4, registry=Registry())
            sizes = []
            orig = b._dispatch_hashed

            async def spy(ids, ns, fut, trace_id=0):
                sizes.append(int(ids.shape[0]))
                await orig(ids, ns, fut, trace_id)

            b._dispatch_hashed = spy
            fut = b.submit_hashed_nowait(
                big, np.ones(big.shape[0], dtype=np.int64))
            out = await fut
            await b.drain()
            b.close()
            return out, sizes

        out, sizes = _run(drive())
        assert sizes == [64, 64, 64, 64, 44]  # carved at max_batch
        assert len(out) == 300 and not out.fail_open
        owners = mesh.owner_of_id(big)
        for dev in range(4):
            idx = np.flatnonzero(owners == dev)
            if not idx.size:
                continue
            oracle = SketchLimiter(cfg, ManualClock(T0))
            ref = oracle.allow_ids(big[idx])
            np.testing.assert_array_equal(out.allowed[idx], ref.allowed)
            np.testing.assert_array_equal(out.remaining[idx], ref.remaining)
            oracle.close()
        hot_decisions = out.allowed[big == hot]
        assert hot_decisions.sum() == 5 and bool(np.all(hot_decisions[:5]))
        # The reassembled result has no packed buffers; the wire
        # encoder's packbits fallback must still frame it losslessly.
        from ratelimiter_tpu.serving import protocol

        assert out.wire_packed is None
        frame = protocol.encode_result_hashed(9, out)
        rt = protocol.parse_result_hashed(frame[protocol.HEADER_SIZE:])
        np.testing.assert_array_equal(rt.allowed, out.allowed)
        np.testing.assert_array_equal(rt.remaining, out.remaining)
        mesh.close()


# ------------------------------------------- snapshot-during-coalesce


class TestSnapshotDuringCoalesce:
    def test_capture_quiesces_inflight_coalesced_windows(self, tmp_path):
        """capture_state while coalesced windows are in flight must
        reflect EVERY launched window (quiescence by data dependence on
        the donated state chain, PR 2/3 contract): restoring the
        snapshot reproduces the post-launch counters exactly."""
        cfg = _cfg(limit=10)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        hot = np.full(4, 0xF00D, dtype=np.uint64)
        # Two coalesced windows (multi-frame concatenations) in flight.
        t1 = mesh.launch_ids(np.concatenate([hot, hot]))
        t2 = mesh.launch_ids(hot)
        path = str(tmp_path / "mid.npz")
        mesh.save(path)  # capture with both windows un-resolved
        assert mesh.resolve(t1).allowed.tolist() == [True] * 8
        assert mesh.resolve(t2).allowed.tolist() == [True, True, False,
                                                     False]
        restored = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        restored.restore(path)
        # 12 units offered in the snapshot, limit 10: nothing left.
        out = restored.allow_ids(hot)
        assert out.allowed.tolist() == [False] * 4
        mesh.close()
        restored.close()


# ----------------------------------------------------- fail-open folding


class TestFailOpenFolding:
    def test_window_or_folds_over_reassembled_frames(self):
        """A coalesced window containing a failed-open sub-frame answers
        EVERY frame of the window with fail_open=True — the conservative
        window-OR (a frame coalesced with a failed-open neighbor cannot
        prove its own answers weren't fabricated), the same OR-folding
        contract as the native door's multi-shard hashed joins."""
        cfg = _cfg(fail_open=True)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        all_ids = np.arange(1, 4096, dtype=np.uint64)
        owners = mesh.owner_of_id(all_ids)
        broken, healthy = 1, 2
        mesh.slices[broken].inject_failure()
        frames = [
            # Frame A never touches the broken slice...
            (all_ids[owners == healthy][:48],
             np.ones(48, dtype=np.int64)),
            # ...frame B does.
            (all_ids[owners == broken][:48],
             np.ones(48, dtype=np.int64)),
        ]
        outs = _coalesce(mesh, frames)
        assert outs[1].fail_open
        assert bool(np.all(outs[1].allowed))  # fabricated allows
        assert outs[0].fail_open, \
            "window OR must reach every reassembled frame"
        mesh.heal()
        mesh.close()

    def test_healthy_window_does_not_or_spuriously(self):
        mesh = SlicedMeshLimiter(_cfg(fail_open=True), ManualClock(T0),
                                 n_devices=4)
        frames = [(np.arange(1 + 64 * i, 65 + 64 * i, dtype=np.uint64),
                   np.ones(64, dtype=np.int64)) for i in range(3)]
        outs = _coalesce(mesh, frames)
        assert not any(o.fail_open for o in outs)
        mesh.close()


# --------------------------------------------- native door segmentation


class TestNativeDoorSegmentation:
    def test_oversized_hashed_frame_segments_and_reassembles(self):
        """The C++ dispatcher must cut a coalesced run BEFORE crossing
        max_batch (the r06 collapse was overshooting runs padding to an
        un-prewarmed shape) — a hashed frame far larger than max_batch is
        carved into max_batch-sized segments, dispatched separately, and
        reassembled through the extended BatchJoin into ONE reply frame
        whose decisions are bit-identical to the single-device oracle."""
        from ratelimiter_tpu.serving.client import Client
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
            native_server_available,
        )
        if not native_server_available():
            pytest.skip("no compiler for the native front door")

        cfg = _cfg(limit=5)
        lim = SketchLimiter(cfg, ManualClock(T0))
        srv = NativeRateLimitServer(lim, max_batch=64, max_delay=1e-4)
        srv.start()
        try:
            rng = np.random.default_rng(23)
            ids = rng.integers(1, 1 << 40, size=300, dtype=np.uint64)
            ids[0::10] = np.uint64(0xCAFE)  # hot id spanning segments
            with Client(port=srv.port, timeout=60.0) as c:
                br = c.allow_hashed(ids)
            assert len(br) == 300  # one reply frame, original order
            # The oracle mirrors the carve: sequential max_batch-sized
            # dispatches (segmentation IS sequential dispatch of the
            # segments — CU collision writes are per-dispatch, so a
            # single 300-id oracle batch would be a different, coarser
            # granularity, not what the scheduler promises).
            oracle = SketchLimiter(cfg, ManualClock(T0))
            refs = [oracle.allow_ids(ids[s:s + 64])
                    for s in range(0, 300, 64)]
            ref_allowed = np.concatenate([r.allowed for r in refs])
            ref_remaining = np.concatenate([r.remaining for r in refs])
            np.testing.assert_array_equal(br.allowed, ref_allowed)
            np.testing.assert_array_equal(br.remaining, ref_remaining)
            # Same-key sequencing across the segment boundaries: the
            # first 5 hot occurrences (and only those) were admitted.
            hot = br.allowed[0::10]
            assert hot.sum() == 5 and bool(np.all(hot[:5]))
            oracle.close()
        finally:
            srv.shutdown()

    def test_oversized_string_frame_segments_and_reassembles(self):
        """The STRING lane gets the same carve (the wire protocol admits
        T_ALLOW_BATCH frames up to ~174K short keys regardless of
        --max-batch, and prewarm only covers one pad shape past it): a
        lone oversized string frame opening a run is carved into
        max_batch segments riding the shard-split BatchJoin deposit
        path, answered as ONE T_RESULT_BATCH frame bit-identical to the
        oracle dispatched segment-sequentially."""
        from ratelimiter_tpu.serving.client import Client
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
            native_server_available,
        )
        if not native_server_available():
            pytest.skip("no compiler for the native front door")

        cfg = _cfg(limit=5)
        lim = SketchLimiter(cfg, ManualClock(T0))
        srv = NativeRateLimitServer(lim, max_batch=64, max_delay=1e-4)
        srv.start()
        try:
            keys = [f"key-{i}" for i in range(300)]
            for i in range(0, 300, 10):
                keys[i] = "hot-key"  # 30 occurrences spanning segments
            with Client(port=srv.port, timeout=60.0) as c:
                out = c.allow_batch(keys)
            assert len(out) == 300  # one reply frame, original order
            oracle = SketchLimiter(cfg, ManualClock(T0))
            refs = []
            for s in range(0, 300, 64):
                refs.extend(oracle.allow_batch(keys[s:s + 64]).results())
            assert [r.allowed for r in out] == [r.allowed for r in refs]
            assert ([r.remaining for r in out]
                    == [r.remaining for r in refs])
            hot = [out[i].allowed for i in range(0, 300, 10)]
            assert sum(hot) == 5 and all(hot[:5])
            oracle.close()
        finally:
            srv.shutdown()

    def test_many_small_frames_coalesce_through_native_door(self):
        """Many clients' small hashed frames ride one server: decisions
        per frame equal the oracle fed the same ids in submission order
        (the in-C++ coalescer merges them; reassembly must keep each
        frame's rows intact)."""
        from ratelimiter_tpu.serving.client import Client
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
            native_server_available,
        )
        if not native_server_available():
            pytest.skip("no compiler for the native front door")

        cfg = _cfg(limit=1 << 20)
        lim = SketchLimiter(cfg, ManualClock(T0))
        srv = NativeRateLimitServer(lim, max_batch=256, max_delay=2e-3)
        srv.start()
        try:
            rng = np.random.default_rng(31)
            frames = [rng.integers(1, 1 << 40, size=32, dtype=np.uint64)
                      for _ in range(16)]
            with Client(port=srv.port, timeout=60.0) as c:
                outs = [c.allow_hashed(f) for f in frames]
            for f, o in zip(frames, outs):
                assert len(o) == len(f)
                assert bool(np.all(o.allowed))
        finally:
            srv.shutdown()


# ------------------------------------------------- debt-slab visibility


class TestDebtSlabGauge:
    def test_gauges_scrape_per_slice_and_healthz_aggregates(self):
        """The debt-slab occupancy/collision surface (ROADMAP item 5:
        strict gating doesn't transfer to the continuously-decaying debt
        slab, visibility does) rides the mesh lane: a token-bucket mesh
        exports one gauge series per device slice via the scrape-time
        collect hook — never on the decide path — and /healthz
        aggregates worst-unit occupancy across slices."""
        from ratelimiter_tpu.serving.__main__ import _debt_slab_health

        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=50,
                     window=10.0,
                     sketch=SketchParams(depth=3, width=256))
        mesh = create_limiter(cfg, backend="mesh", clock=clock, n_devices=4)
        reg = Registry()
        dec = MetricsDecorator(mesh, registry=reg)
        rng = np.random.default_rng(41)
        dec.allow_ids(rng.integers(1, 1 << 40, size=512, dtype=np.uint64),
                      np.full(512, 30, dtype=np.int64))

        text = reg.render()  # the scrape runs the collect hook
        occ = reg.get("rate_limiter_debt_slab_occupancy")
        assert occ is not None
        per_slice = [occ.value(shard="0", slice=str(i)) for i in range(4)]
        assert any(v > 0 for v in per_slice), per_slice
        assert "rate_limiter_debt_slab_collision_probability" in text

        h = _debt_slab_health([dec])
        assert h["debt_slab"]["units"] == 4
        assert h["debt_slab"]["occupancy"] == pytest.approx(
            max(per_slice), abs=1e-9)
        assert 0.0 <= h["debt_slab"]["collision_p"] <= 1.0
        # Idle long enough and the decayed slab reads empty again — the
        # gauge tracks EFFECTIVE debt, not stale stored cells.
        clock.advance(3600.0)
        assert _debt_slab_health([dec])["debt_slab"]["occupancy"] == 0.0
        mesh.close()

    def test_windowed_sketch_has_no_debt_slab(self):
        from ratelimiter_tpu.serving.__main__ import _debt_slab_health

        lim = SketchLimiter(_cfg(), ManualClock(T0))
        assert _debt_slab_health([lim]) == {}
        lim.close()


# -------------------------------------------------------- pinned smoke


class TestCoalescerSmoke:
    def test_coalesced_window_not_slower_than_fork_join_on_cpu(self):
        """Pinned throughput smoke: dispatching K mixed frames as ONE
        coalesced window (single partition + per-device sub-dispatch +
        one barrier + rows() scatter-back) must not be slower than K
        fork-join dispatches on the CPU harness. The margin absorbs
        shared-box scheduler noise — the claim guarded is 'coalescing is
        at worst free', the measured win on this image is ~Kx fewer
        per-device dispatches."""
        cfg = _cfg(limit=1 << 20)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        rng = np.random.default_rng(3)
        frames = [rng.integers(1, 1 << 40, size=256, dtype=np.uint64)
                  for _ in range(8)]
        window = np.concatenate(frames)
        mesh.allow_ids(window)  # compile both pad shapes
        mesh.allow_ids(frames[0])
        reps = 6

        t0 = time.perf_counter()
        for _ in range(reps):
            for f in frames:
                mesh.resolve(mesh.launch_ids(f))
        fork_join_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        for _ in range(reps):
            res = mesh.resolve(mesh.launch_ids(window))
            off = 0
            for f in frames:
                res.rows(off, len(f))
                off += len(f)
        coalesced_s = time.perf_counter() - t0

        assert coalesced_s <= fork_join_s * 1.5, (
            f"coalescer regressed: window {coalesced_s:.4f}s vs "
            f"fork-join {fork_join_s:.4f}s over {reps} windows")
        mesh.close()
