"""The exact backend across the chips of a host (ISSUE 51): ``--backend
dense --mesh-devices N`` mounts N device-pinned ``DenseLimiter`` slices,
a key's row on the chip that owns its hash — held here, on four virtual
CPU devices, to the plain reference (``ExactLimiter``: ONE map over all
keys, knowing nothing of slices) row for row, and to the share rule: a
key sits in exactly one slice's directory, the one the shared owner rule
names.

The owner rule is CALLED, never restated: ``SlicedMeshLimiter
.owner_of_id`` (what the composite routes by) is held to ``ops.hashing
.splitmix64`` once, below, and to the native door's router by the
served test.
"""

from __future__ import annotations

import os
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

import jax

from tests.contract import ContractTests

from ratelimiter_tpu import (
    Algorithm,
    CheckpointError,
    Config,
    DenseParams,
    InvalidConfigError,
    ManualClock,
    MeshSpec,
    create_limiter,
)
from ratelimiter_tpu.algorithms.dense import DenseLimiter
from ratelimiter_tpu.ops import directory
from ratelimiter_tpu.ops.hashing import splitmix64
from ratelimiter_tpu.parallel import SlicedMeshLimiter, build_slices

from netutil import free_port

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 (virtual) devices")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T0 = 1_700_000_000.5
N = 4
ALGOS = {"bucket": Algorithm.TOKEN_BUCKET,
         "fixed": Algorithm.FIXED_WINDOW,
         "sliding": Algorithm.SLIDING_WINDOW}
every_rule = pytest.mark.parametrize("algo", list(ALGOS))


def cfg_of(algo, *, capacity=1024, limit=5, window=60.0, **kw):
    return Config(algorithm=ALGOS[algo], limit=limit, window=window,
                  dense=DenseParams(capacity=capacity),
                  mesh=MeshSpec(devices=N), **kw)


def pair(algo, **kw):
    """(four-slice dense mesh, exact, clock): one rule, one clock."""
    clock = ManualClock(T0)
    cfg = cfg_of(algo, **kw)
    return (create_limiter(cfg, backend="dense", clock=clock),
            create_limiter(cfg, backend="exact", clock=clock), clock)


def names(ids) -> list:
    return [f"id{int(i)}" for i in ids]


def zipf_frames(seed: int, frames: int, rows: int, keys: int):
    """Zipf ids with in-frame duplicates; every frame owns rows on all
    four slices (asserted by the callers through ``ticket.subs``)."""
    rng = np.random.default_rng(seed)
    for _ in range(frames):
        yield (rng.zipf(1.2, size=rows) % keys + 1).astype(np.uint64)


def held(slice_) -> np.ndarray:
    """The finalized keys a slice's directory holds (host view)."""
    keys = slice_._dir_keys().view(np.uint64).ravel()
    return keys[(keys != directory.EMPTY) & (keys != directory.TOMB)]


# ------------------------------------------------------- the contract


class TestDenseMeshContract(ContractTests):
    """The interface suite on the four-slice composite."""

    backend = "dense"
    supports_failure_injection = True

    def make_limiter(self, config: Config, clock):
        return create_limiter(replace(config, mesh=MeshSpec(devices=N)),
                              backend="dense", clock=clock)

    def inject_failure(self, lim) -> None:
        lim.inject_failure()


# --------------------------------------------- the oracle and the share


@every_rule
def test_the_mesh_decides_as_one_exact_map_row_for_row(algo):
    """The same stream to both: ``allowed`` and ``remaining`` (and the
    two time columns) equal for every row, four tickets in flight."""
    mesh, exact, clock = pair(algo)
    assert isinstance(mesh, SlicedMeshLimiter) and mesh.n_slices == N
    pending = []
    for step, ids in enumerate(zipf_frames(11, 24, 160, 600)):
        ticket = mesh.launch_ids(ids)
        assert [s for s, _, _ in ticket.subs] == list(range(N)), \
            "a frame that does not fan out to every slice"
        assert len(np.unique(ids)) < len(ids)            # duplicates
        pending.append((ticket, exact.allow_batch(names(ids)), step))
        if len(pending) == 4:
            for t, want, s in pending:
                got = mesh.resolve(t)
                for col in ("allowed", "remaining", "retry_after",
                            "reset_at"):
                    np.testing.assert_array_equal(
                        getattr(got, col), getattr(want, col),
                        err_msg=f"{col} at frame {s}")
            pending.clear()
        clock.advance(1.7)
    assert not pending
    mesh.close()
    exact.close()


@every_rule
def test_every_key_sits_in_the_one_slice_the_owner_rule_names(algo):
    mesh, _, _ = pair(algo)
    frames = list(zipf_frames(5, 12, 200, 900))
    for ids in frames:
        mesh.allow_ids(ids)
    seen = np.unique(np.concatenate(frames))
    final = splitmix64(seen)
    # The composite's rule is the shared one: splitmix64(id) % n.
    owner = mesh.owner_of_id(seen)
    np.testing.assert_array_equal(
        owner, (final % np.uint64(N)).astype(np.int64))
    tables = [held(s) for s in mesh.slices]
    for i, keys in enumerate(tables):
        np.testing.assert_array_equal(np.sort(keys),
                                      np.sort(final[owner == i]))
    assert sum(len(t) for t in tables) == len(seen) == mesh.key_count()
    stats = mesh.directory_stats()
    assert stats["entries"] == len(seen) and stats["unplaced"] == 0
    assert stats["capacity"] == N * 1024
    # Each slice's whole state on its own device, and nothing elsewhere.
    devices = jax.devices()[:N]
    for s, dev in zip(mesh.slices, devices):
        for leaf in s._state.values():
            assert leaf.devices() == {dev} and leaf.committed
    assert mesh.state_resident_bytes() == {
        dev: mesh.slices[0].memory_bytes() for dev in devices}
    mesh.close()


def test_a_pinned_table_is_built_on_its_device_and_the_unpinned_as_before():
    cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=5, window=60.0,
                 dense=DenseParams(capacity=256))
    dev = jax.devices()[2]
    pinned, plain = DenseLimiter(cfg, device=dev), DenseLimiter(cfg)
    for name, leaf in pinned._state.items():
        assert leaf.devices() == {dev} and leaf.committed, name
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(plain._state[name]))
    assert all(not leaf.committed for leaf in plain._state.values())
    # Staged batches, the override table and the controls follow it.
    pinned.set_override("vip", 9)
    ticket = pinned.launch_ids(np.arange(1, 40, dtype=np.uint64))
    assert ticket.outs.devices() == {dev}
    assert pinned.resolve(ticket).allowed.all()
    assert all(v.devices() == {dev} for v in pinned._policy_dev.values())
    pinned.reset("vip")
    assert pinned.prune() == 0 and pinned.key_count() == 39
    assert all(leaf.devices() == {dev} for leaf in pinned._state.values())
    pinned.close()
    plain.close()


# ------------------------------------------------------------- controls


def test_controls_reach_the_slices_they_concern():
    mesh, exact, clock = pair("bucket", limit=3)
    keys = [f"user:{i}" for i in range(40)]
    for lim in (mesh, exact):
        lim.allow_batch(keys)
        lim.allow_batch(keys)
    owner = mesh.owner_of_key("user:7")
    before = [s.key_count() for s in mesh.slices]
    passes = [s.directory_stats()["reclaim_passes"] for s in mesh.slices]
    mesh.reset("user:7")
    exact.reset("user:7")
    after = [s.key_count() for s in mesh.slices]
    assert [b - a for b, a in zip(before, after)] \
        == [int(i == owner) for i in range(N)]          # its owner only
    assert [s.directory_stats()["reclaim_passes"] - p
            for s, p in zip(mesh.slices, passes)] \
        == [int(i == owner) for i in range(N)]
    assert mesh.allow("user:7").remaining == exact.allow("user:7").remaining
    mesh.update_limit(6)                                 # every slice
    exact.update_limit(6)
    assert [s.config.limit for s in mesh.slices] == [6] * N
    got, want = mesh.allow_batch(keys), exact.allow_batch(keys)
    np.testing.assert_array_equal(got.allowed, want.allowed)
    np.testing.assert_array_equal(got.remaining, want.remaining)
    mesh.set_override("user:3", 50)
    assert all(s.get_override("user:3") is not None for s in mesh.slices)
    clock.advance(200.0)                                 # idle: all expire
    held_before = mesh.key_count()
    assert mesh.prune() == held_before > 0               # every slice
    assert mesh.key_count() == 0
    mesh.close()
    exact.close()


def test_each_slice_reclaims_for_itself_and_no_row_is_left_unplaced():
    """A rehearsal capacity smaller than the stream's keys: 4 x 128
    entries for 2,400 keys that expire as the stream goes (window 1 s).
    Each slice runs its own pass at its own gate, under its own lock,
    and the mesh still answers as the plain rule."""
    mesh, exact, clock = pair("bucket", capacity=128, window=1.0, limit=4)
    rng = np.random.default_rng(3)
    for step in range(60):
        ids = (rng.integers(0, 40, size=96) + 40 * step + 1).astype(np.uint64)
        got, want = mesh.allow_ids(ids), exact.allow_batch(names(ids))
        np.testing.assert_array_equal(got.allowed, want.allowed, str(step))
        np.testing.assert_array_equal(got.remaining, want.remaining)
        assert not got.fail_open
        clock.advance(0.25)
    stats = [s.directory_stats() for s in mesh.slices]
    assert sum(st["inserts"] for st in stats) > N * 128   # more than fits
    assert all(st["unplaced"] == 0 for st in stats)
    assert all(st["reclaim_passes"] >= 2 for st in stats), stats
    assert all(st["entries"] <= 128 for st in stats)
    total = mesh.directory_stats()
    assert total["reclaimed"] == sum(st["reclaimed"] for st in stats) > 0
    mesh.close()
    exact.close()


# ----------------------------------------------- construction and refusal


def test_build_slices_is_the_one_seam_and_the_factory_goes_through_it():
    cfg = cfg_of("bucket")
    slices = build_slices(cfg, backend="dense")
    assert [type(s) for s in slices] == [DenseLimiter] * N
    assert [s._device for s in slices] == jax.devices()[:N]
    assert all(s._capacity == 1024 for s in slices)      # a slice's entries
    for s in slices:
        s.close()
    with pytest.raises(InvalidConfigError, match="no device slices"):
        build_slices(cfg, backend="exact")
    # One table without mesh devices, as before; slices with them.
    one = create_limiter(replace(cfg, mesh=MeshSpec()), backend="dense")
    assert type(one) is DenseLimiter and one._device is None
    one.close()
    by_kwarg = create_limiter(replace(cfg, mesh=MeshSpec()),
                              backend="dense", n_devices=2)
    assert by_kwarg.n_slices == 2
    by_kwarg.close()
    with pytest.raises(InvalidConfigError, match="collective router"):
        create_limiter(replace(cfg, mesh=MeshSpec(devices=N,
                                                  router="collective")),
                       backend="dense")


def test_the_combined_snapshot_is_refused_by_name(tmp_path):
    mesh = create_limiter(cfg_of("bucket"), backend="dense")
    for call in (mesh.capture_state,
                 lambda: mesh.restore(str(tmp_path / "x.npz")),
                 lambda: mesh.restore_slice(str(tmp_path / "x.npz"), 0)):
        with pytest.raises(CheckpointError, match="mesh:dense"):
            call()
    mesh.close()


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO
    return env


_DENSE4 = ["--backend", "dense", "--mesh-devices", "4",
           "--dense-capacity", "1024"]


@pytest.mark.parametrize("flags, said", [
    (_DENSE4 + ["--router", "collective"],
     "--router collective cannot carry --backend dense"),
    (_DENSE4 + ["--native", "--router", "collective"],
     "--router collective cannot carry --backend dense"),
    (_DENSE4 + ["--snapshot-dir", "/nonexistent"],
     "--snapshot-dir is not supported with --backend dense --mesh-devices"),
    (_DENSE4 + ["--native", "--shards", "2"],
     "use --mesh-devices, not --shards"),
    (_DENSE4 + ["--quarantine"], "--quarantine needs --backend mesh"),
    (["--backend", "exact", "--algorithm", "token_bucket",
      "--mesh-devices", "4"], "--mesh-devices needs --backend mesh"),
    (["--backend", "sketch", "--mesh-devices", "4"],
     "--mesh-devices needs --backend mesh"),
    (["--backend", "dense", "--router", "collective"],
     "--router needs --backend mesh"),
], ids=["collective", "collective-native", "snapshot-dir", "shards",
        "quarantine", "exact", "sketch", "router-without-devices"])
def test_the_flag_rules_refuse_by_name(flags, said):
    proc = subprocess.run(
        [sys.executable, "-m", "ratelimiter_tpu.serving",
         "--port", str(free_port())] + flags,
        env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert said in proc.stderr, proc.stderr[-2000:]


# -------------------------------------------------------------- served


def _serve(extra, log):
    """The server binary over four dense slices; its stderr goes to the
    file ``log`` (a pipe nobody drains fills up and stops the server)."""
    return subprocess.Popen(
        [sys.executable, "-m", "ratelimiter_tpu.serving", "--port", "0",
         "--algorithm", "token_bucket", "--limit", "5", "--window", "60",
         "--max-batch", "64"] + _DENSE4 + extra,
        env=_env(), stdout=subprocess.PIPE, stderr=log, text=True)


def _banner(proc, log) -> str:
    for line in proc.stdout:
        if line.startswith("serving"):
            return line
    log.seek(0)
    raise AssertionError(f"no banner; stderr: {log.read()[-3000:]}")


@pytest.mark.parametrize("door", ["native", "asyncio"])
def test_both_doors_serve_four_dense_slices(door, tmp_path):
    """The normal served path, through the binary: the banner names four
    devices, the id lane answers as the plain rule, and (native door,
    the slices mounted as its dispatch shards) ``/metrics`` carries each
    directory and memory family once a slice."""
    import re

    from ratelimiter_tpu.serving import Client

    native = door == "native"
    if native:
        from ratelimiter_tpu.serving.native_server import (
            native_server_available,
        )
        if not native_server_available():
            pytest.skip("no compiler for the native front door")
    log = open(tmp_path / "server.stderr", "w+")
    proc = _serve(["--native", "--flight-recorder"] if native else [], log)
    try:
        banner = _banner(proc, log)
        assert "slice_devices=0,1,2,3" in banner, banner
        port = int(re.search(r" on \S+:(\d+) ", banner).group(1))
        exact = create_limiter(
            Config(algorithm=Algorithm.TOKEN_BUCKET, limit=5, window=60.0),
            backend="exact")
        with Client(port=port, timeout=60.0) as c:
            for ids in zipf_frames(9, 6, 200, 300):
                got = c.allow_hashed(ids)
                want = exact.allow_batch(names(ids))
                np.testing.assert_array_equal(got.allowed, want.allowed)
                np.testing.assert_array_equal(got.remaining, want.remaining)
            text = c.metrics()
        exact.close()
        from chipbench import promtext

        samples = promtext.parse(text)

        def labels(family):
            return [dict(lab) for (name, lab), _ in samples.items()
                    if name == family]

        resident = labels("rate_limiter_state_resident_bytes")
        assert sorted(l["device"] for l in resident) == list("0123")
        if native:
            assert sorted(l["shard"] for l in resident) == list("0123")
            assert all(l["shard"] == l["device"] for l in resident)
            for name in ("entries", "capacity", "lookups_total",
                         "probes_total", "inserts_total", "unplaced_total",
                         "reclaim_passes_total"):
                family = "rate_limiter_directory_" + name
                assert sorted(l["shard"] for l in labels(family)) \
                    == list("0123"), family
            assert promtext.total(
                samples, "rate_limiter_directory_capacity") == 4 * 1024
            assert promtext.total(
                samples, "rate_limiter_directory_unplaced_total") == 0
            # The prewarm ran a span a slice, and they overlapped.
            prewarm = labels("rate_limiter_stage_seconds_count")
            assert any(l.get("stage") == "prewarm" for l in prewarm)
        else:
            assert promtext.total(
                samples, "rate_limiter_directory_capacity") == 4 * 1024
    finally:
        proc.terminate()
        proc.wait(timeout=60)
        log.close()


@pytest.fixture
def recorder():
    from ratelimiter_tpu.observability import tracing

    tracing.disable()
    rec = tracing.enable(4096)
    try:
        yield rec
    finally:
        tracing.disable()


def test_prewarm_warms_the_slices_side_by_side(recorder):
    """One ``prewarm`` row a slice in the ring, ``shard`` its place, and
    the rows overlap in time: the slices did not wait for one another."""
    from ratelimiter_tpu.serving.__main__ import _prewarm

    slices = build_slices(cfg_of("bucket"), backend="dense")
    _prewarm(slices, 32)
    rows = [r for r in recorder.dump() if r["stage"] == "prewarm"]
    assert sorted(r["shard"] for r in rows) == list(range(N))
    assert len({r["thread"] for r in rows}) == N
    assert max(r["t_start_ns"] for r in rows) \
        < min(r["t_end_ns"] for r in rows)
    # Prewarm's made-up ids were given up before the server serves.
    assert [s.key_count() for s in slices] == [0] * N
    for s in slices:
        s.close()


def test_prewarm_of_one_target_stays_on_the_calling_thread(recorder):
    """The one-chip start: one target, no pool — its ``prewarm`` row is
    shard 0 on the thread that called, and it too gives up the made-up
    ids."""
    import threading

    from ratelimiter_tpu.serving.__main__ import _prewarm

    one = DenseLimiter(cfg_of("bucket"))
    _prewarm(one, 32)
    rows = [r for r in recorder.dump() if r["stage"] == "prewarm"]
    assert [(r["shard"], r["thread"]) for r in rows] \
        == [(0, threading.current_thread().name)]
    assert one.key_count() == 0
    one.close()


def test_a_slice_that_fails_to_warm_fails_the_start():
    """Side by side does not mean unheard: the start raises what the
    slice raised."""
    from ratelimiter_tpu.serving.__main__ import _prewarm

    slices = build_slices(cfg_of("bucket"), backend="dense")
    slices[2].close()
    with pytest.raises(Exception, match="closed"):
        _prewarm(slices, 32)
    for s in slices:
        s.close()


@pytest.mark.parametrize("backend", ["sketch", "dense"])
def test_the_composite_has_the_directory_controls_of_its_backend(backend):
    """``prune`` / ``key_count`` / ``directory_stats`` come as one: a mesh
    of exact slices has all three, a sketch mesh (whose slices hold no
    key) none — ``hasattr`` is what the door and MetricsDecorator ask."""
    mesh = SlicedMeshLimiter(cfg_of("bucket"), backend=backend)
    for name in ("prune", "key_count", "directory_stats"):
        assert hasattr(mesh, name) == (backend == "dense"), name
    mesh.close()
