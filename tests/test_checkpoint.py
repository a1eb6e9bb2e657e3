"""Checkpoint/restore: crash-equivalence and staleness-contract tests.

The done-criterion (VERDICT r2 item 3): decide -> snapshot -> fresh
limiter -> restore -> decisions consistent with an uncrashed control
limiter, modulo the documented staleness window (decisions after the
snapshot are lost; the restored limiter errs toward allowing).
"""

import numpy as np
import pytest

from ratelimiter_tpu import (
    Algorithm,
    CheckpointError,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)

T0 = 1_700_000_000.0


def pair(algo, backend, limit=10, window=60.0, **kw):
    """(limiter, control) with independent ManualClocks at T0."""
    mk = lambda: create_limiter(
        Config(algorithm=algo, limit=limit, window=window, **kw),
        backend=backend, clock=ManualClock(T0))
    return mk, mk()


BACKEND_ALGOS = [
    ("exact", Algorithm.FIXED_WINDOW),
    ("exact", Algorithm.SLIDING_WINDOW),
    ("exact", Algorithm.TOKEN_BUCKET),
    ("dense", Algorithm.FIXED_WINDOW),
    ("dense", Algorithm.SLIDING_WINDOW),
    ("dense", Algorithm.TOKEN_BUCKET),
    ("sketch", Algorithm.TPU_SKETCH),
    ("sketch", Algorithm.FIXED_WINDOW),
    ("sketch", Algorithm.TOKEN_BUCKET),
]


class TestCrashEquivalence:
    @pytest.mark.parametrize("backend,algo", BACKEND_ALGOS,
                             ids=lambda v: str(v))
    def test_restore_matches_uncrashed_control(self, backend, algo, tmp_path):
        """Same op sequence on (snapshot -> crash -> restore) and on an
        uncrashed control must yield identical decisions."""
        path = str(tmp_path / "snap.npz")
        mk, control = pair(algo, backend, limit=10)
        victim = mk()

        ops1 = [("a", 3), ("b", 7), ("a", 4), ("c", 1)]
        for k, n in ops1:
            assert (victim.allow_n(k, n).allowed
                    == control.allow_n(k, n).allowed)
        victim.save(path)
        victim.close()  # the crash

        restored = mk()
        restored.restore(path)
        # Post-restore decisions must match the control step for step —
        # including denials that depend on pre-crash consumption.
        ops2 = [("a", 4), ("a", 3), ("b", 3), ("b", 1), ("c", 9), ("d", 10)]
        for k, n in ops2:
            rv, rc = restored.allow_n(k, n), control.allow_n(k, n)
            assert rv.allowed == rc.allowed, (k, n)
            assert rv.remaining == rc.remaining, (k, n)
        restored.close()
        control.close()

    @pytest.mark.parametrize("backend,algo", BACKEND_ALGOS,
                             ids=lambda v: str(v))
    def test_elapsed_time_catches_up(self, backend, algo, tmp_path):
        """Restoring a snapshot older than the full history horizon behaves
        like a fresh limiter: quotas fully recovered (window expiry or
        bucket refill), nothing stuck."""
        path = str(tmp_path / "snap.npz")
        clock = ManualClock(T0)
        cfg = Config(algorithm=algo, limit=5, window=10.0)
        lim = create_limiter(cfg, backend=backend, clock=clock)
        assert lim.allow_n("k", 5).allowed
        assert not lim.allow("k").allowed
        lim.save(path)
        lim.close()

        clock2 = ManualClock(T0 + 25.0)  # > 2 windows later
        lim2 = create_limiter(cfg, backend=backend, clock=clock2)
        lim2.restore(path)
        assert lim2.allow_n("k", 5).allowed  # full quota back
        lim2.close()

    def test_lost_tail_errs_toward_allowing(self, tmp_path):
        """Decisions AFTER the snapshot are lost: the restored limiter may
        re-admit them (under-count), never over-deny relative to its own
        snapshot — the documented direction."""
        path = str(tmp_path / "snap.npz")
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=10, window=60.0)
        clock = ManualClock(T0)
        lim = create_limiter(cfg, backend="sketch", clock=clock)
        assert lim.allow_n("k", 4).allowed
        lim.save(path)
        assert lim.allow_n("k", 6).allowed   # after snapshot: lost
        assert not lim.allow("k").allowed
        lim.close()

        lim2 = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
        lim2.restore(path)
        res = lim2.allow_n("k", 6)
        assert res.allowed               # the lost 6 are re-admittable
        assert not lim2.allow("k").allowed
        lim2.close()


class TestMeshCheckpoint:
    def test_mesh_save_restore_preserves_replication(self, tmp_path):
        """Sharding-preserving restore on the mesh: snapshot a replicated
        state, restore into a fresh mesh limiter, decisions continue with
        the global invariant intact."""
        import jax
        import pytest as _pytest

        if len(jax.devices()) < 8:
            _pytest.skip("needs 8 virtual devices")
        from ratelimiter_tpu.parallel import MeshSketchLimiter, make_mesh

        mesh = make_mesh(n_devices=8)
        path = str(tmp_path / "mesh.npz")
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=10, window=60.0,
                     sketch=SketchParams(depth=2, width=256, sub_windows=6))
        lim = MeshSketchLimiter(cfg, ManualClock(T0), mesh=mesh,
                                merge="gather")
        assert lim.allow_batch(["hot"] * 16).allow_count == 10
        lim.save(path)
        lim.close()

        lim2 = MeshSketchLimiter(cfg, ManualClock(T0), mesh=mesh,
                                 merge="gather")
        lim2.restore(path)
        out = lim2.allow_batch(["hot"] * 16)
        assert out.allow_count == 0          # global history restored
        assert lim2.allow_batch(["cold"] * 4).allow_count == 4
        lim2.close()


class TestValidation:
    def test_config_fingerprint_mismatch(self, tmp_path):
        path = str(tmp_path / "snap.npz")
        c1 = Config(algorithm=Algorithm.TPU_SKETCH, limit=10, window=60.0)
        lim = create_limiter(c1, backend="sketch", clock=ManualClock(T0))
        lim.allow("k")
        lim.save(path)
        lim.close()

        c2 = Config(algorithm=Algorithm.TPU_SKETCH, limit=11, window=60.0)
        lim2 = create_limiter(c2, backend="sketch", clock=ManualClock(T0))
        with pytest.raises(CheckpointError, match="fingerprint"):
            lim2.restore(path)
        lim2.close()

    def test_geometry_change_rejected(self, tmp_path):
        path = str(tmp_path / "snap.npz")
        c1 = Config(algorithm=Algorithm.TPU_SKETCH, limit=10, window=60.0,
                    sketch=SketchParams(depth=2, width=1024))
        lim = create_limiter(c1, backend="sketch", clock=ManualClock(T0))
        lim.save(path)
        lim.close()
        c2 = Config(algorithm=Algorithm.TPU_SKETCH, limit=10, window=60.0,
                    sketch=SketchParams(depth=2, width=2048))
        lim2 = create_limiter(c2, backend="sketch", clock=ManualClock(T0))
        with pytest.raises(CheckpointError, match="fingerprint"):
            lim2.restore(path)
        lim2.close()

    def test_kind_mismatch(self, tmp_path):
        path = str(tmp_path / "snap.npz")
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=10, window=60.0)
        create_limiter(cfg, backend="exact", clock=ManualClock(T0)).save(path)
        dense = create_limiter(cfg, backend="dense", clock=ManualClock(T0))
        with pytest.raises(CheckpointError, match="kind"):
            dense.restore(path)
        dense.close()

    def test_not_a_checkpoint(self, tmp_path):
        path = tmp_path / "junk.npz"
        np.savez(path, a=np.arange(3))
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=10, window=60.0)
        lim = create_limiter(cfg, backend="exact", clock=ManualClock(T0))
        with pytest.raises(CheckpointError):
            lim.restore(str(path))
        lim.close()

    def test_dense_slot_map_round_trips(self, tmp_path):
        path = str(tmp_path / "snap.npz")
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=10, window=60.0)
        clock = ManualClock(T0)
        lim = create_limiter(cfg, backend="dense", clock=clock, capacity=64)
        for i in range(40):
            lim.allow(f"user:{i}")
        assert lim.key_count() == 40
        lim.save(path)
        lim.close()
        # The keyspace directory lives on the device (ADR-027): its key
        # column is saved with the state columns, one entry a key; the
        # host's old slot map (slot_keys / slot_ids / last_used) is gone.
        with np.load(path) as snap:
            assert {"state_dir_keys", "state_cols"} <= set(snap.files)
            assert snap["state_cols"].shape == (3, 65)   # tokens, rem, last
            assert not {"slot_keys", "slot_ids", "last_used"} & set(
                snap.files)
            assert snap["state_dir_keys"].size == 64
            assert np.count_nonzero(snap["state_dir_keys"]) == 40
        lim2 = create_limiter(cfg, backend="dense", clock=ManualClock(T0),
                              capacity=64)
        lim2.restore(path)
        assert lim2.key_count() == 40
        # Slot reuse still works post-restore: new keys claim free slots.
        for i in range(40, 64):
            assert lim2.allow(f"user:{i}").allowed
        lim2.close()

    def test_dense_capacity_mismatch(self, tmp_path):
        path = str(tmp_path / "snap.npz")
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=10, window=60.0)
        lim = create_limiter(cfg, backend="dense", clock=ManualClock(T0),
                             capacity=64)
        lim.save(path)
        lim.close()
        lim2 = create_limiter(cfg, backend="dense", clock=ManualClock(T0),
                              capacity=128)
        with pytest.raises(CheckpointError, match="capacity"):
            lim2.restore(path)
        lim2.close()


class TestDenseSnapshotAcrossLayouts:
    """ISSUE 43: the dense state is 32-bit words on the device, the
    snapshot FILE stays what the int64 layout wrote — ``state_cols
    int64[K, C+1]`` and ``state_dir_keys int64[NB, W]`` — converted once
    on the host, so a snapshot crosses the change in both directions."""

    CAPACITY, LANES, LIMIT, SPENT = 64, 4, 5, 3
    #: Finalized 64-bit ids: small, above 2**32, negative as int64, and a
    #: pair that shares each word with another key.
    IDS = np.array([7, (1 << 32) + 7, (9 << 32) + 7, (9 << 32) + 8,
                    (1 << 63) + 5, (1 << 64) - 2, 0xDEADBEEF12345678,
                    (1 << 63) | (1 << 31)], np.uint64)

    def config(self, algo):
        from ratelimiter_tpu import DenseParams

        return Config(algorithm=algo, limit=self.LIMIT, window=60.0,
                      dense=DenseParams(capacity=self.CAPACITY,
                                        lanes=self.LANES, probe_bound=16))

    def by_hand(self, algo):
        """The arrays the int64 layout held after every key of IDS spent
        SPENT at T0, written down here with no help from the backend:
        each key in the first free lane of its home bucket, its state
        row beside it, every other row pristine."""
        import jax.numpy as jnp

        from ratelimiter_tpu.ops import directory

        nb, w = self.CAPACITY // self.LANES, self.LANES
        lo = jnp.asarray((self.IDS & np.uint64(0xFFFFFFFF)).astype(np.uint32))
        hi = jnp.asarray((self.IDS >> np.uint64(32)).astype(np.uint32))
        homes = np.asarray(directory.home((lo, hi), nb, w)[0])
        micros, t0_us = 1_000_000, int(T0 * 1_000_000)
        win = t0_us // (60 * micros) * (60 * micros)
        fresh, used = {
            Algorithm.TOKEN_BUCKET: (
                (self.LIMIT * micros, 0, 0),
                ((self.LIMIT - self.SPENT) * micros, 0, t0_us)),
            Algorithm.FIXED_WINDOW: ((0, 0), (self.SPENT, win)),
            Algorithm.SLIDING_WINDOW: ((0, 0, 0), (self.SPENT, 0, win)),
        }[algo]
        cols = np.tile(np.array(fresh, np.int64)[:, None],
                       (1, self.CAPACITY + 1))
        keys = np.zeros((nb, w), np.int64)
        for key, b in zip(self.IDS.view(np.int64), homes):
            lane = int(np.argmin(keys[b] != 0))
            assert keys[b, lane] == 0, "home bucket full: pick other ids"
            keys[b, lane] = key
            cols[:, b * w + lane] = used
        assert (keys < 0).sum() >= 4 and (keys > 1 << 32).sum() >= 2
        return {"state_cols": cols, "state_dir_keys": keys}

    def write(self, path, lim, arrays):
        from ratelimiter_tpu.checkpoint import save_state

        save_state(path, "dense", lim.config, arrays,
                   {"saved_at": T0, "capacity": self.CAPACITY})

    @pytest.mark.parametrize("algo", [Algorithm.TOKEN_BUCKET,
                                      Algorithm.FIXED_WINDOW,
                                      Algorithm.SLIDING_WINDOW],
                             ids=lambda a: a.value)
    def test_an_int64_snapshot_restores_and_is_written_back_byte_equal(
            self, algo, tmp_path):
        path, again = str(tmp_path / "old.npz"), str(tmp_path / "new.npz")
        arrays = self.by_hand(algo)
        clock = ManualClock(T0)
        dense = create_limiter(self.config(algo), backend="dense",
                               clock=clock)
        self.write(path, dense, arrays)
        dense.restore(path)
        assert dense.key_count() == self.IDS.shape[0]
        # On the device: words. In the file: what was read, byte for byte.
        assert {str(v.dtype) for v in dense._state.values()} == {"uint32"}
        kind, written, extra = dense.capture_state()
        assert kind == "dense" and extra["capacity"] == self.CAPACITY
        for name, want in arrays.items():
            assert written[name].dtype == np.int64
            assert written[name].shape == want.shape
            assert written[name].tobytes() == want.tobytes(), name
        # ... and through a file of its own, into a second limiter.
        dense.save(again)
        twin = create_limiter(self.config(algo), backend="dense",
                              clock=clock)
        twin.restore(again)
        for name, want in arrays.items():
            assert twin.capture_state()[1][name].tobytes() == want.tobytes()
        # The restored state decides as the plain rule that lived the
        # same history: every key spent SPENT at T0.
        exact = create_limiter(self.config(algo), backend="exact",
                               clock=clock)
        names = [f"k{int(i)}" for i in self.IDS]
        assert exact.allow_batch(names, [self.SPENT] * len(names)
                                 ).allowed.all()
        rng = np.random.default_rng(43)
        new = np.array([11, (3 << 32) + 7, (1 << 63) + 6], np.uint64)
        for step in range(5):
            clock.advance(float(rng.uniform(0.5, 20.0)))
            ids = rng.choice(np.concatenate([self.IDS, new]), size=24)
            want = exact.allow_batch([f"k{int(i)}" for i in ids])
            for lim in (dense, twin):
                got = lim.resolve(lim.launch_hashed(ids))
                for col in ("allowed", "remaining", "retry_after",
                            "reset_at"):
                    np.testing.assert_array_equal(
                        getattr(got, col), getattr(want, col),
                        err_msg=f"{col} step {step}")
        assert dense.key_count() == exact.key_count()
        for lim in (dense, twin, exact):
            lim.close()

    def test_a_wrong_shape_is_still_refused(self, tmp_path):
        algo = Algorithm.TOKEN_BUCKET
        arrays = self.by_hand(algo)
        dense = create_limiter(self.config(algo), backend="dense",
                               clock=ManualClock(T0))
        path = str(tmp_path / "wide.npz")
        self.write(path, dense, dict(
            arrays, state_dir_keys=arrays["state_dir_keys"].reshape(8, 8)))
        with pytest.raises(CheckpointError,
                           match=r"directory of shape \(8, 8\) != this "
                                 r"limiter's \(16, 4\)"):
            dense.restore(path)
        path = str(tmp_path / "words.npz")
        self.write(path, dense, {"state_cols": arrays["state_cols"],
                                "state_dir_lo": arrays["state_dir_keys"]})
        with pytest.raises(CheckpointError, match="state arrays"):
            dense.restore(path)
        assert dense.key_count() == 0
        dense.close()


class TestCrashAtomicSave:
    """ISSUE-2 satellite: save_state is crash-atomic on its own — tmp
    write + fsync(file) + os.replace + fsync(dir). A failure injected
    mid-write must leave the previous snapshot byte-identical and no
    tmp litter behind."""

    def _good_snapshot(self, tmp_path):
        path = str(tmp_path / "snap.npz")
        mk, lim = pair(Algorithm.SLIDING_WINDOW, "exact")
        lim.allow_n("a", 7)
        lim.save(path)
        with open(path, "rb") as f:
            golden = f.read()
        return path, mk, lim, golden

    def test_fsync_failure_mid_write_keeps_old_snapshot(
            self, tmp_path, monkeypatch):
        import os as _os

        path, mk, lim, golden = self._good_snapshot(tmp_path)
        lim.allow_n("a", 1)                       # state changed since

        real_fsync = _os.fsync

        def boom(fd):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr("ratelimiter_tpu.checkpoint.os.fsync", boom)
        with pytest.raises(OSError):
            lim.save(path)
        monkeypatch.setattr("ratelimiter_tpu.checkpoint.os.fsync",
                            real_fsync)
        with open(path, "rb") as f:
            assert f.read() == golden              # old snapshot intact
        assert [p for p in tmp_path.iterdir()
                if ".tmp." in p.name] == []        # no tmp litter
        restored = mk()
        restored.restore(path)                     # and still loadable
        assert not restored.allow_n("a", 4).allowed
        restored.close()
        lim.close()

    def test_replace_failure_keeps_old_snapshot(self, tmp_path,
                                                monkeypatch):
        path, mk, lim, golden = self._good_snapshot(tmp_path)

        def boom(src, dst):
            raise OSError("injected replace failure")

        monkeypatch.setattr("ratelimiter_tpu.checkpoint.os.replace", boom)
        with pytest.raises(OSError, match="injected"):
            lim.save(path)
        monkeypatch.undo()
        with open(path, "rb") as f:
            assert f.read() == golden
        assert [p for p in tmp_path.iterdir()
                if ".tmp." in p.name] == []
        lim.close()


class TestGoldenFingerprint:
    """ISSUE-2 satellite: config_fingerprint is pinned to a golden value.

    Every existing snapshot carries its config's fingerprint; ANY change
    to the hash inputs (renamed/added/removed Config fields, changed
    serialization) strands all of them. If this test fails and the
    change was ACCIDENTAL, fix the code until it passes. If the change
    is INTENTIONAL (a new semantic config field must participate), bump
    checkpoint.FORMAT_VERSION, update the golden values below in the
    same commit, and say in the commit message that existing snapshots
    are invalidated.
    """

    GOLDEN = "9ce0bf0e02550dc074f2925212dccb29"

    def test_golden_value(self):
        from ratelimiter_tpu.checkpoint import config_fingerprint

        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=100,
                     window=60.0)
        fp = config_fingerprint(cfg)
        assert fp == self.GOLDEN, (
            f"config_fingerprint drifted: {fp} != {self.GOLDEN}. This "
            "STRANDS every existing snapshot (restore refuses on "
            "fingerprint mismatch). If unintentional, revert the Config/"
            "fingerprint change; if intentional, bump FORMAT_VERSION and "
            "update TestGoldenFingerprint.GOLDEN in the same commit.")

    def test_persistence_spec_is_excluded(self):
        """Snapshot cadence is operational, not state geometry: changing
        it must NOT strand snapshots."""
        from ratelimiter_tpu import PersistenceSpec
        from ratelimiter_tpu.checkpoint import config_fingerprint

        base = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=100,
                      window=60.0)
        tuned = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=100,
                       window=60.0,
                       persistence=PersistenceSpec(
                           dir="/elsewhere", snapshot_interval=1.0,
                           retain=9, wal_fsync="never"))
        assert config_fingerprint(base) == config_fingerprint(tuned)

    def test_semantic_fields_do_participate(self):
        from dataclasses import replace

        from ratelimiter_tpu.checkpoint import config_fingerprint

        base = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=100,
                      window=60.0)
        for changed in (replace(base, limit=101),
                        replace(base, window=61.0),
                        replace(base, algorithm=Algorithm.FIXED_WINDOW),
                        replace(base, sketch=SketchParams(width=1 << 17))):
            assert config_fingerprint(changed) != config_fingerprint(base)


class TestBackCompat:
    def test_bucket_checkpoint_without_acc_restores(self, tmp_path):
        """The v0.1 token-bucket snapshot had no `acc` (DCN export
        accumulator): it must restore with a zero accumulator instead of
        failing the key-set check (upgrade path)."""
        mk, lim = pair(Algorithm.TOKEN_BUCKET, "sketch")
        lim.allow_n("k", 7)
        path = str(tmp_path / "old.npz")
        lim.save(path)
        # Rewrite the snapshot as a pre-`acc` release would have laid
        # it out (same meta, `acc` array absent).
        with np.load(path, allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files if k != "acc"}
        np.savez(path, **arrays)
        lim2 = mk()
        lim2.restore(path)
        # The defaulted accumulator exports nothing stale.
        from ratelimiter_tpu.parallel.dcn import export_debt

        assert export_debt(lim2).sum() == 0
        assert lim2.allow_n("k", 3).allowed        # 7 + 3 = limit
        assert not lim2.allow("k").allowed
        lim.close()
        lim2.close()
