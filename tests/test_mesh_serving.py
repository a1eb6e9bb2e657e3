"""Slice-parallel serving tests (ISSUE-5 acceptance, ADR-012).

The mesh backend = one device-pinned single-chip limiter per device,
keys hash-routed to their owning slice, decide path collective-free.
The load-bearing invariant: for the keys a device owns, its decisions
are BIT-IDENTICAL to a single-device limiter fed exactly that traffic —
pinned here per lane (string, pre-hashed, raw-id) and per door
(asyncio + native), plus the durability story (sharded snapshot,
kill -9 recovery, re-bucketing restore across a device-count change —
ADR-018; the full reshard oracle lives in tests/test_reshard.py) and a
loose scaling smoke. CI runs this file in an explicit 8-virtual-device lane
with zero skips allowed (ci.yml).
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from ratelimiter_tpu import (
    Algorithm,
    CheckpointError,
    Config,
    ManualClock,
    MeshSpec,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.algorithms.sketch import (
    SketchLimiter,
    SketchTokenBucketLimiter,
)
from ratelimiter_tpu.parallel import SlicedMeshLimiter, build_slices

from netutil import free_port

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 (virtual) devices")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


# ------------------------------------------------------- routing oracle


class TestSliceOracle:
    def test_string_lane_bit_identical_to_per_slice_oracle(self):
        """Each slice's decisions == a single-device limiter fed ONLY the
        keys that slice owns, bit for bit (allowed/remaining/retry/
        reset). This is the acceptance wording verbatim: collective-free
        routing means a device never sees foreign keys, so its sketch
        evolves exactly like a standalone chip's."""
        cfg = _cfg(limit=5)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        rng = np.random.default_rng(3)
        keys = [f"k{int(i)}" for i in rng.integers(0, 60, size=240)]
        out = mesh.allow_batch(keys)

        owners = mesh.owner_of_hash(mesh._hash(keys))
        for dev in range(4):
            idx = np.flatnonzero(owners == dev)
            if not idx.size:
                continue
            oracle = SketchLimiter(cfg, ManualClock(T0))
            ref = oracle.allow_batch([keys[i] for i in idx])
            np.testing.assert_array_equal(out.allowed[idx], ref.allowed)
            np.testing.assert_array_equal(out.remaining[idx], ref.remaining)
            np.testing.assert_array_equal(out.retry_after[idx],
                                          ref.retry_after)
            np.testing.assert_array_equal(out.reset_at[idx], ref.reset_at)
            oracle.close()
        mesh.close()

    def test_raw_id_lane_bit_identical_to_per_slice_oracle(self):
        cfg = _cfg(limit=3)
        mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        rng = np.random.default_rng(5)
        ids = rng.integers(1, 1 << 40, size=300, dtype=np.uint64)
        out = mesh.allow_ids(ids)

        owners = mesh.owner_of_id(ids)
        for dev in range(4):
            idx = np.flatnonzero(owners == dev)
            if not idx.size:
                continue
            oracle = SketchLimiter(cfg, ManualClock(T0))
            ref = oracle.allow_ids(ids[idx])
            np.testing.assert_array_equal(out.allowed[idx], ref.allowed)
            np.testing.assert_array_equal(out.remaining[idx], ref.remaining)
            oracle.close()
        mesh.close()

    def test_same_key_sequencing_survives_the_split(self):
        """A hot key's requests inside one frame land on its slice in
        frame order (the stable-sort partition), so exactly `limit` are
        admitted and they are the FIRST `limit` occurrences."""
        mesh = SlicedMeshLimiter(_cfg(limit=7), ManualClock(T0), n_devices=4)
        keys = []
        for i in range(40):
            keys.append("hot")
            keys.append(f"cold{i}")
        out = mesh.allow_batch(keys)
        hot = out.allowed[0::2]
        assert hot.sum() == 7
        assert bool(np.all(hot[:7])) and not bool(np.any(hot[7:]))
        mesh.close()

    def test_scalar_and_reset_route_to_owner(self):
        clock = ManualClock(T0)
        mesh = SlicedMeshLimiter(_cfg(limit=2), clock, n_devices=4)
        assert mesh.allow("one").allowed
        assert mesh.allow("one").allowed
        assert not mesh.allow("one").allowed
        mesh.reset("one")
        assert mesh.allow("one").allowed
        mesh.close()


# -------------------------------------------------- pipelined dispatch


class TestMeshPipeline:
    def test_launch_resolve_matches_sync_and_is_idempotent(self):
        cfg = _cfg(limit=5)
        c1, c2 = ManualClock(T0), ManualClock(T0)
        a = SlicedMeshLimiter(cfg, c1, n_devices=4)
        b = SlicedMeshLimiter(cfg, c2, n_devices=4)
        rng = np.random.default_rng(11)
        frames = [[f"k{int(i)}" for i in rng.integers(0, 30, size=64)]
                  for _ in range(4)]
        tickets = [a.launch_batch(f) for f in frames]
        outs_pipe = [a.resolve(t) for t in tickets]
        outs_sync = [b.allow_batch(f) for f in frames]
        for p, s in zip(outs_pipe, outs_sync):
            np.testing.assert_array_equal(p.allowed, s.allowed)
            np.testing.assert_array_equal(p.remaining, s.remaining)
        # idempotent resolve
        again = a.resolve(tickets[0])
        assert again is outs_pipe[0]
        a.close()
        b.close()

    def test_single_owner_wire_frame_passes_device_packed_buffers(self):
        """A frame fully owned by one slice keeps the zero-copy
        wire_packed buffers (the composite must not strip them), and a
        MIXED wire frame reassembles packed buffers through the index
        maps (ADR-013 scatter-back) — the wire encoder frames either
        from packed columns, never by re-packing per row."""
        mesh = SlicedMeshLimiter(_cfg(), ManualClock(T0), n_devices=4)
        ids = np.arange(1, 4000, dtype=np.uint64)
        owners = mesh.owner_of_id(ids)
        mine = ids[owners == 2][:64]
        res = mesh.resolve(mesh.launch_ids(mine, wire=True))
        assert res.wire_packed is not None
        # A mixed frame reassembles the packed form host-side via the
        # scatter-back: buffers present and bit-consistent with the
        # row-level columns.
        res2 = mesh.resolve(mesh.launch_ids(ids[:64], wire=True))
        assert res2.wire_packed is not None
        bits, words, padded = res2.wire_packed
        b = len(res2)
        np.testing.assert_array_equal(
            np.unpackbits(bits, bitorder="little")[:b].astype(bool),
            res2.allowed)
        np.testing.assert_array_equal(words[:b], res2.remaining)
        np.testing.assert_array_equal(
            words[padded:padded + b].view(np.float64), res2.retry_after)
        np.testing.assert_array_equal(
            words[2 * padded:2 * padded + b].view(np.float64),
            res2.reset_at)
        mesh.close()

    def test_fail_open_split_frame_ors_the_flag(self):
        mesh = SlicedMeshLimiter(_cfg(fail_open=True), ManualClock(T0),
                                 n_devices=4)
        ids = np.arange(1, 200, dtype=np.uint64)
        # Break ONE slice: its sub-frame fails open; the whole frame's
        # flag must say so (same contract as the native door's
        # multi-shard joins).
        mesh.slices[1].inject_failure()
        out = mesh.allow_ids(ids)
        assert out.fail_open
        owners = mesh.owner_of_id(ids)
        assert bool(np.all(out.allowed[owners == 1]))
        mesh.heal()
        mesh.close()


# ------------------------------------------------------- control plane


class TestMeshControlPlane:
    def test_policy_overrides_apply_everywhere_and_decide(self):
        clock = ManualClock(T0)
        mesh = SlicedMeshLimiter(_cfg(limit=2), clock, n_devices=4)
        mesh.set_override("vip", 6)
        assert mesh.get_override("vip").limit == 6
        out = mesh.allow_batch(["vip"] * 8)
        assert out.allow_count == 6
        assert mesh.delete_override("vip") is True
        assert mesh.get_override("vip") is None
        assert mesh.override_count() == 0
        mesh.close()

    def test_update_limit_and_window_reach_every_slice(self):
        clock = ManualClock(T0)
        mesh = SlicedMeshLimiter(_cfg(limit=2), clock, n_devices=4)
        mesh.update_limit(4)
        assert mesh.config.limit == 4
        for s in mesh.slices:
            assert s.config.limit == 4
        out = mesh.allow_batch(["w"] * 6)
        assert out.allow_count == 4
        mesh.update_window(30.0)
        assert mesh.config.window == 30.0
        for s in mesh.slices:
            assert s.config.window == 30.0
        mesh.close()

    def test_token_bucket_mesh_refill(self):
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=10, window=10.0,
                     sketch=SketchParams(depth=2, width=256))
        mesh = create_limiter(cfg, backend="mesh", clock=clock, n_devices=4)
        assert isinstance(mesh.slices[0], SketchTokenBucketLimiter)
        out = mesh.allow_batch(["hot"] * 16)
        assert out.allow_count == 10
        clock.advance(2.0)
        out = mesh.allow_batch(["hot"] * 4)
        assert out.allow_count == 2
        mesh.close()

    def test_factory_and_mesh_spec(self):
        from dataclasses import replace

        cfg = replace(_cfg(), mesh=MeshSpec(devices=2))
        mesh = create_limiter(cfg, backend="mesh", clock=ManualClock(T0))
        assert mesh.n_slices == 2
        mesh.close()


# --------------------------------------------------- durability × mesh


class TestMeshCheckpoint:
    def test_capture_restore_roundtrip(self, tmp_path):
        clock = ManualClock(T0)
        cfg = _cfg(limit=4)
        mesh = SlicedMeshLimiter(cfg, clock, n_devices=4)
        keys = [f"k{i}" for i in range(40)]
        mesh.allow_batch(keys)
        mesh.set_override("vip", 9)
        path = str(tmp_path / "mesh.npz")
        mesh.save(path)

        fresh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
        fresh.restore(path)
        # Restored counters: the consumed quota stands on every slice.
        a = mesh.allow_batch(keys)
        b = fresh.allow_batch(keys)
        np.testing.assert_array_equal(a.allowed, b.allowed)
        assert fresh.get_override("vip").limit == 9
        mesh.close()
        fresh.close()

    def test_restore_rebuckets_device_count_change(self, tmp_path):
        """A snapshot taken at another slice count RE-BUCKETS onto this
        mesh (ADR-018; the pre-PR-11 refusal is gone): overrides exact,
        counters carried, never over-admitting vs the source — the full
        oracle lives in tests/test_reshard.py. restore_slice still
        refuses (one slice cannot re-bucket in place)."""
        cfg = _cfg(limit=4)
        clock = ManualClock(T0)
        mesh = SlicedMeshLimiter(cfg, clock, n_devices=4)
        keys = [f"k{i}" for i in range(40)]
        mesh.allow_batch(keys)
        mesh.set_override("vip", 9)
        path = str(tmp_path / "mesh4.npz")
        mesh.save(path)
        src = mesh.allow_batch(keys)
        mesh.close()
        other = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=2)
        other.restore(path)
        assert other.get_override("vip").limit == 9
        got = other.allow_batch(keys)
        assert not (got.allowed & ~src.allowed).any()
        other.close()
        with pytest.raises(CheckpointError, match="rebucket"):
            third = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=2)
            try:
                third.restore_slice(path, 0)
            finally:
                third.close()


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    return env


def _spawn_mesh(port, snap_dir, mesh_devices=2, extra=()):
    argv = [sys.executable, "-m", "ratelimiter_tpu.serving",
            "--backend", "mesh", "--mesh-devices", str(mesh_devices),
            "--limit", "100", "--window", "600",
            "--sketch-depth", "4", "--sketch-width", "8192",
            "--sub-windows", "6",
            "--port", str(port), "--snapshot-dir", snap_dir,
            "--snapshot-interval", "500", "--no-prewarm", *extra]
    return subprocess.Popen(argv, env=_env(), stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _wait_banner(proc, timeout=120):
    t0 = time.time()
    lines = []
    while time.time() - t0 < timeout:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        if line.startswith("serving"):
            return lines
    raise AssertionError("server never served:\n" + "".join(lines))


class TestMeshKillNine:
    def test_kill9_recovers_sharded_state_same_device_count(self, tmp_path):
        """A mesh-backed server snapshots its sliced state, dies by
        SIGKILL under live traffic, and restores onto the SAME device
        count: overrides recover exactly via WAL replay, counters are
        bounded (restored >= pre-snapshot consumption, <= true total —
        under-count only, the fail-toward-allowing direction)."""
        from ratelimiter_tpu.serving.client import Client

        snap_dir = str(tmp_path / "mesh-durable")
        port = free_port()
        proc = _spawn_mesh(port, snap_dir)
        try:
            _wait_banner(proc)
            c = Client(port=port, timeout=120.0)
            assert c.allow_n("k", 30).allowed
            c.set_override("vip", 42)
            snap_id, wal_seq, _dur = c.snapshot()
            assert snap_id >= 1 and wal_seq >= 1
            stop = threading.Event()

            def hammer():
                try:
                    with Client(port=port, timeout=120.0) as hc:
                        i = 0
                        while not stop.is_set():
                            hc.allow(f"bg:{i % 97}")
                            i += 1
                except (ConnectionError, OSError):
                    pass
            t = threading.Thread(target=hammer, daemon=True)
            t.start()
            for _ in range(5):
                assert c.allow_n("k", 10).allowed
            c.set_override("vip2", 9)
            assert c.delete_override("vip") is True
            time.sleep(0.2)
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
            stop.set()
            t.join(timeout=10)
            c.close()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

        proc2 = _spawn_mesh(port, snap_dir)
        try:
            lines = _wait_banner(proc2)
            assert any("recovery" in ln for ln in lines)
            with Client(port=port, timeout=120.0) as c2:
                assert c2.get_override("vip2") == (9, 1.0)
                assert c2.get_override("vip") is None
                # >= 30 consumed (snapshot restored the owning slice) ...
                assert not c2.allow_n("k", 71).allowed
                # ... and <= 80 (under-count only).
                assert c2.allow_n("k", 20).allowed
            proc2.send_signal(signal.SIGTERM)
            rc = proc2.wait(timeout=30)
            # Graceful exit is rc 0; the XLA CPU client very rarely
            # crashes in its own atexit teardown AFTER the server has
            # fully drained + snapshotted (every correctness assertion
            # above already passed). Both observed flavors of that
            # teardown crash are tolerated — SIGABRT (the common one)
            # and SIGSEGV (seen once under full-suite load, PR 9: the
            # same XLA-CPU destructor class, after the final snapshot
            # line had already been emitted). The JAX-free exact-backend
            # kill -9 test (test_durability_crash.py) pins rc == 0 for
            # the serving stack itself, so widening this gate does not
            # mask a real shutdown regression — the durability
            # assertions above are the test's contract, not the XLA
            # destructor's exit code.
            assert rc in (0, -signal.SIGABRT, -signal.SIGSEGV), (
                f"shutdown rc={rc}:\n{proc2.stdout.read()}")
        finally:
            if proc2.poll() is None:
                proc2.kill()
                proc2.wait()

    @pytest.mark.slow
    def test_device_count_change_rebuckets_on_restart(self, tmp_path):
        """Restarting a mesh snapshot directory under a DIFFERENT device
        count RE-BUCKETS the key-routed state onto the new geometry
        (ADR-018; pre-PR-11 this refused): the server boots, logs the
        re-bucketing warning, and serves with the restored counters —
        the consumed quota stands across the resize. Slow lane (two
        server boots); the mesh CI lane runs it unfiltered."""
        from ratelimiter_tpu.serving.client import Client

        snap_dir = str(tmp_path / "mesh-resize")
        port = free_port()
        proc = _spawn_mesh(port, snap_dir)
        try:
            _wait_banner(proc)
            with Client(port=port, timeout=120.0) as c:
                # Consume the whole default limit (100) on one key.
                assert c.allow_n("k", 100).allowed
                c.snapshot()
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

        port2 = free_port()
        proc2 = _spawn_mesh(port2, snap_dir, mesh_devices=4)
        try:
            lines = _wait_banner(proc2)
            assert any("re-bucketing" in ln for ln in lines), lines
            with Client(port=port2, timeout=120.0) as c2:
                # The re-bucketed state still carries the consumed
                # quota: the key stays denied (never over-admits).
                assert not c2.allow_n("k", 1).allowed
        finally:
            if proc2.poll() is None:
                proc2.kill()
                proc2.wait()


# ----------------------------------------------------------- both doors


class TestMeshDoors:
    def test_asyncio_door_serves_all_three_lanes(self):
        import asyncio

        from ratelimiter_tpu.serving.client import AsyncClient
        from ratelimiter_tpu.serving.server import RateLimitServer

        cfg = _cfg(limit=5)
        oracle_cfg = cfg

        async def main():
            lim = SlicedMeshLimiter(cfg, n_devices=4)
            srv = RateLimitServer(lim, max_delay=1e-4)
            await srv.start()
            c = await AsyncClient.connect(port=srv.port)
            outs = await asyncio.gather(*[c.allow("hot") for _ in range(8)])
            assert sum(o.allowed for o in outs) == 5
            res = await c.allow_batch([f"b{i}" for i in range(64)])
            assert len(res) == 64
            ids = np.arange(1, 257, dtype=np.uint64)
            br = await c.allow_hashed(ids)
            direct = SlicedMeshLimiter(oracle_cfg, n_devices=4)
            np.testing.assert_array_equal(br.allowed,
                                          direct.allow_ids(ids).allowed)
            direct.close()
            await c.close()
            await srv.shutdown()
            lim.close()

        asyncio.run(main())

    def test_native_door_mounts_slices_as_shards(self):
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
            native_server_available,
        )
        if not native_server_available():
            pytest.skip("no compiler for the native front door")
        from ratelimiter_tpu.serving.client import Client

        cfg = _cfg(limit=5)
        slices = build_slices(cfg, n_devices=4)
        srv = NativeRateLimitServer(slices[0], shards=4,
                                    shard_limiters=slices, max_delay=1e-4)
        srv.start()
        try:
            with Client(port=srv.port, timeout=60.0) as c:
                assert sum(c.allow("hot").allowed for _ in range(8)) == 5
                ids = np.arange(1, 1025, dtype=np.uint64)
                br = c.allow_hashed(ids)
                direct = SlicedMeshLimiter(cfg, n_devices=4)
                np.testing.assert_array_equal(
                    br.allowed, direct.allow_ids(ids).allowed)
                direct.close()
            st = srv.stats()
            assert st["num_shards"] == 4
            assert sum(st["shard_decisions"]) == st["decisions_total"]
            assert all(v > 0 for v in st["shard_decisions"]), \
                "per-device routing left a device idle"
        finally:
            srv.shutdown(close_limiters=False)
            for s in slices:
                s.close()

    def test_dcn_peer_gate_accepts_mesh_rejects_host_backends(self):
        """ISSUE-5 satellite: the --dcn-peer argparse gate must accept
        --backend mesh (slices export over DCN) and keep refusing
        non-sketch-family backends."""
        env = _env()
        # exact: refused before any server starts (fast, JAX-free).
        proc = subprocess.run(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "exact", "--algorithm", "sliding_window",
             "--dcn-peer", "127.0.0.1:1", "--port", str(free_port())],
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert "sketch-family" in proc.stderr
        # mesh: passes the gate and serves (banner appears).
        port = free_port()
        srv = subprocess.Popen(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "mesh", "--mesh-devices", "2",
             "--sketch-depth", "2", "--sketch-width", "1024",
             "--sub-windows", "6", "--no-prewarm",
             "--dcn-peer", "127.0.0.1:1", "--port", str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            _wait_banner(srv)
        finally:
            srv.terminate()
            srv.wait(timeout=30)

    def test_mesh_devices_flag_needs_mesh_backend(self):
        env = _env()
        proc = subprocess.run(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "sketch", "--mesh-devices", "2",
             "--port", str(free_port())],
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert "--mesh-devices needs --backend mesh" in proc.stderr


# ------------------------------------------ a frame reaches its slices


class TestFrameReachesEverySliceOnce:
    """What scaling across devices rests on, in counts: a mixed frame is
    cut by ``splitmix64(id) % n`` into one part a slice that owns a row,
    each part is ONE launch on that slice (and none on a slice that owns
    nothing), and every row is decided exactly once."""

    N = 4

    def _frames(self):
        from ratelimiter_tpu.ops.hashing import splitmix64

        def owners(a):
            return (splitmix64(a) % np.uint64(self.N)).astype(np.int64)

        ids = np.arange(1, 1025, dtype=np.uint64)
        # A mixed frame, then one whose rows two of the slices own.
        for frame in (ids, ids[np.isin(owners(ids), (1, 3))][:300]):
            yield frame, np.bincount(owners(frame), minlength=self.N)

    def test_the_python_router_launches_once_a_slice_that_owns_a_row(self):
        mesh = SlicedMeshLimiter(_cfg(limit=5), ManualClock(T0),
                                 n_devices=self.N)
        try:
            for frame, split in self._frames():
                before = [s.result_fetches for s in mesh.slices]
                ticket = mesh.launch_ids(frame)
                assert [s for s, _, _ in ticket.subs] \
                    == [s for s in range(self.N) if split[s]]
                owners = mesh.owner_of_id(frame)
                for s, pos, sub in ticket.subs:
                    np.testing.assert_array_equal(
                        pos, np.flatnonzero(owners == s))
                    assert sub.outs.devices() \
                        == {mesh.slices[s]._device}
                out = mesh.resolve(ticket)
                assert out.allowed.shape == frame.shape
                launched = [s.result_fetches - b
                            for s, b in zip(mesh.slices, before)]
                assert launched == [int(n > 0) for n in split]
        finally:
            mesh.close()

    def test_the_native_doors_router_does_the_same_split(self):
        from ratelimiter_tpu.serving.client import Client
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
            native_server_available,
        )
        if not native_server_available():
            pytest.skip("no compiler for the native front door")

        slices = build_slices(_cfg(limit=5), n_devices=self.N)
        srv = NativeRateLimitServer(slices[0], shards=self.N,
                                    shard_limiters=slices, max_delay=1e-4)
        srv.start()
        try:
            with Client(port=srv.port, timeout=60.0) as c:
                for frame, split in self._frames():
                    before = srv.stats()["shard_decisions"]
                    fetched = [s.result_fetches for s in slices]
                    out = c.allow_hashed(frame)
                    assert len(out) == len(frame)
                    after = srv.stats()["shard_decisions"]
                    assert [a - b for a, b in zip(after, before)] \
                        == split.tolist()
                    assert [s.result_fetches - f
                            for s, f in zip(slices, fetched)] \
                        == [int(n > 0) for n in split]
        finally:
            srv.shutdown(close_limiters=False)
            for s in slices:
                s.close()
