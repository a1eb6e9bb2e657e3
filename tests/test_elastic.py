"""Elastic fleet lifecycle (ADR-018): live range migration, adopted-unit
durability, automatic rejoin give-back, graceful departure, and the
chaos scenarios that break them mid-flight.

The in-process tests build real FleetCore/FleetForwarder/FleetMembership
stacks per host with a patched frame transport (payload-level protocol,
deterministic ManualClock) — the same shape TestInProcessFleetOracle
uses; the wire itself is covered by the slow two-process tests below and
in tests/test_fleet.py.

Pinned invariants:

* a migrated range's counters CONTINUE on the receiver (capture ->
  WAL-suffix replay -> flip; overrides exact, loss bounded by the
  handoff window, under-count only);
* exactly ONE owner per bucket range per epoch, under kill/abort at
  every injected handoff phase;
* the adopted-range standby rides the successor's own snapshot cycle
  (the ADR-017 declared leftover): original owner dies -> successor
  adopts -> successor snapshots -> successor dies -> ITS successor
  restores the adopted overrides exactly;
* a returning host gets its ranges back automatically (auto rejoin)
  with the state the successor accumulated while covering for it.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from ratelimiter_tpu import (
    Algorithm,
    Config,
    PersistenceSpec,
    SketchParams,
)
from ratelimiter_tpu.chaos import injector as chaos_injector
from ratelimiter_tpu.core.clock import ManualClock
from ratelimiter_tpu.fleet import (
    FleetCore,
    FleetForwarder,
    FleetMap,
    FleetMembership,
    build_standby,
)
from ratelimiter_tpu.fleet.config import FleetHost
from ratelimiter_tpu.observability.metrics import Registry
from ratelimiter_tpu.persistence import PersistenceManager
from tests.netutil import free_port

jax = pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(dir_=None, limit=20):
    return Config(algorithm=Algorithm.SLIDING_WINDOW, limit=limit,
                  window=600.0,
                  sketch=SketchParams(depth=2, width=1024, sub_windows=6),
                  persistence=PersistenceSpec(dir=dir_))


class _Host:
    """One in-process fleet member: persistence + core + forwarder +
    membership, with frame delivery patched to direct calls."""

    def __init__(self, name, fleet_map, clock, tmp_path, hosts):
        self.name = name
        self.clock = clock
        self.dir = str(tmp_path / f"snap-{name}")
        cfg = _cfg(self.dir)
        self.persist = PersistenceManager(cfg.persistence)
        from ratelimiter_tpu.algorithms.sketch import SketchLimiter

        self.limiter = self.persist.wrap(SketchLimiter(cfg, clock))
        self.cfg = self.limiter.config
        self.core = FleetCore(fleet_map, name, prefix=self.cfg.prefix,
                              registry=Registry())
        self.fwd = FleetForwarder(self.limiter, self.core)
        self.persist.attach([self.limiter])
        self.persist.recover()
        self.hosts = hosts

        def restore_fn(payload):
            dir_ = payload.get("snapshot_dir")
            if not dir_:
                return None
            return build_standby(self.cfg, dir_,
                                 origin=payload.get("origin"),
                                 clock=clock)

        def adopt_fn(dead):
            if dead.snapshot_dir:
                return build_standby(self.cfg, dead.snapshot_dir,
                                     clock=clock)
            from ratelimiter_tpu import create_limiter

            return create_limiter(self.cfg, backend="sketch",
                                  clock=clock)

        self.membership = FleetMembership(
            self.core, heartbeat=0.1, dead_after=0.5,
            adopt_fn=adopt_fn,
            snapshot_fn=self.persist.snapshot_now,
            handoff_restore_fn=restore_fn,
            on_adopt=lambda o, u, r: self.persist.add_aux_unit(o, u, r),
            on_release=self.persist.remove_aux_unit,
            registry=Registry())
        self.membership._push_frame = self._push

    def _push(self, host, payload):
        peer = self.hosts.get(host.id)
        if peer is None:
            raise ConnectionError(f"peer {host.id} down")
        if payload.get("kind") == "handoff":
            # Synchronous for test determinism (production runs it on a
            # handoff thread off the receive path).
            peer.membership._handle_handoff(payload)
        else:
            peer.membership.handle_announce(payload)

    def kill(self):
        """kill -9: drop off the transport; no final snapshot, no
        graceful close. The one divergence from a real SIGKILL is that
        the OS would release the WAL flock at process exit — emulate
        that by closing the log fd, nothing else."""
        self.hosts.pop(self.name, None)
        self._killed = True
        self.persist.wal.close()

    def close(self):
        self.hosts.pop(self.name, None)
        self.fwd.close()
        if not getattr(self, "_killed", False):
            self.persist.stop(final_snapshot=False)


def _make_fleet(tmp_path, names, clock, buckets=48):
    per = buckets // len(names)
    hosts_spec = []
    for i, n in enumerate(names):
        lo = i * per
        hi = buckets if i == len(names) - 1 else (i + 1) * per
        hosts_spec.append(FleetHost(
            id=n, host="127.0.0.1", port=i + 1, ranges=((lo, hi),),
            successor=names[(i + 1) % len(names)],
            snapshot_dir=str(tmp_path / f"snap-{n}")))
    m = FleetMap(buckets=buckets, hosts=tuple(hosts_spec))
    m.validate()
    hosts: dict = {}
    for n in names:
        hosts[n] = _Host(n, m, clock, tmp_path, hosts)
    return m, hosts


def _owned_key(core, ordinal, prefix="k"):
    return next(f"{prefix}:{i}" for i in range(500)
                if int(core.owners_of_hash(
                    core.hash_keys([f"{prefix}:{i}"]))[0]) == ordinal)


def _rejoin_and_wait(membership, epoch, timeout=10.0):
    """Kick the give-back (it runs on its own thread so the heartbeat
    keeps beating) and wait for the flip to land."""
    membership._maybe_rejoin()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if membership.core.map.epoch >= epoch:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"rejoin flip to epoch {epoch} never landed "
        f"(at {membership.core.map.epoch})")


class TestLiveMigration:
    def test_counters_and_overrides_continue_on_receiver(self, tmp_path):
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:
            hot = _owned_key(a.core, 0)
            vip = _owned_key(a.core, 0, "vip")
            for _ in range(15):
                a.fwd.allow_n(hot, 1)
            a.fwd.set_override(vip, 7)
            ranges = m.host("a").ranges
            assert a.membership.migrate_ranges(ranges, "b", wait=2.0)
            assert a.core.map.epoch == 2
            assert b.core.map.epoch == 2
            assert b.core.map.host("b").ranges == tuple(
                sorted(set(m.host("b").ranges) | set(ranges)))
            # The receiver CONTINUES the sequence: 5 of 20 left.
            seq = [b.fwd.allow_n(hot, 1) for _ in range(7)]
            assert [r.allowed for r in seq] == [True] * 5 + [False] * 2
            assert b.fwd.get_override(vip).limit == 7
            assert b.membership.handoffs == 1
        finally:
            a.close()
            b.close()

    def test_departure_hands_everything_to_successor(self, tmp_path):
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:
            hot = _owned_key(a.core, 0)
            for _ in range(20):
                a.fwd.allow_n(hot, 1)
            assert a.membership.depart(wait=2.0)
            assert b.core.map.owned_buckets("b") == m.buckets
            assert a.core.map.host("a").ranges == ()
            # b serves the departed range with the restored counters.
            assert not b.fwd.allow_n(hot, 1).allowed
        finally:
            a.close()
            b.close()

    def test_unrelated_epoch_bump_does_not_confirm_flip(self, tmp_path):
        """Flip confirmation is ownership-level: an unrelated epoch
        bump landing during the wait (a failover elsewhere) must not
        make migrate_ranges report success for a move whose handoff
        never reached the receiver."""
        from dataclasses import replace as _replace

        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:
            a.membership._push_frame = lambda host, payload: None  # dropped
            bumped = _replace(m, epoch=m.epoch + 1)

            def bump_soon():
                time.sleep(0.1)
                a.membership.handle_announce(
                    {"kind": "announce", "from": "b",
                     "map": bumped.to_dict()})

            t = threading.Thread(target=bump_soon, daemon=True)
            t.start()
            assert not a.membership.migrate_ranges(
                m.host("a").ranges, "b", wait=0.5)
            t.join(timeout=5)
            # Epoch moved, ownership did not — and a still serves.
            assert a.core.map.epoch == 2
            assert a.core.map.host("a").ranges == m.host("a").ranges
        finally:
            a.close()
            b.close()

    def test_equal_epoch_conflict_converges_on_canonical_winner(
            self, tmp_path):
        """Two uncoordinated movers can mint the SAME epoch: every
        member adopts the deterministic canonical winner regardless of
        arrival order, so the fleet converges instead of splitting."""
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:
            m1 = m.move_ranges(m.host("a").ranges, "a", "b")
            m2 = m.move_ranges(m.host("b").ranges, "b", "a")
            assert m1.epoch == m2.epoch == m.epoch + 1
            winner = min((m1, m2), key=lambda x: x.canonical_key())
            for host_obj, first, second in ((a, m1, m2), (b, m2, m1)):
                host_obj.membership.handle_announce(
                    {"kind": "announce", "from": "x",
                     "map": first.to_dict()})
                host_obj.membership.handle_announce(
                    {"kind": "announce", "from": "y",
                     "map": second.to_dict()})
            assert a.core.map.to_dict() == winner.to_dict()
            assert b.core.map.to_dict() == winner.to_dict()
        finally:
            a.close()
            b.close()

    def test_restore_failure_aborts_live_handoff(self, tmp_path):
        """Unlike dead-owner failover (fresh state beats no service), a
        LIVE move whose standby restore fails ABORTS before the epoch
        bump: the giver still holds the exact counters, so flipping to
        fresh state would hand every moved key a full quota for
        nothing."""
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:

            def broken_restore(payload):
                raise RuntimeError("snapshot volume blip")

            b.membership.handoff_restore_fn = broken_restore
            assert not a.membership.migrate_ranges(
                m.host("a").ranges, "b", wait=0.3)
            assert a.core.map.epoch == 1
            assert b.core.map.epoch == 1
            assert a.core.map.host("a").ranges == m.host("a").ranges
        finally:
            a.close()
            b.close()

    def test_depart_with_no_live_peer_keeps_ownership(self, tmp_path):
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:
            b.kill()
            a.membership._dead.add("b")
            assert not a.membership.depart(wait=0.2)
            assert a.core.map.host("a").ranges == m.host("a").ranges
        finally:
            a.close()
            b.close()


class TestAdoptedUnitDurability:
    def test_second_failure_restores_adopted_overrides_exactly(
            self, tmp_path):
        """The ADR-017 declared leftover, now closed: A dies -> B
        adopts -> B snapshots (aux rides its own cycle) -> B dies ->
        C restores from B's dir and still has A's overrides exactly
        and A's counters (within one snapshot interval)."""
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b", "c"], clock)
        a, b, c = hosts["a"], hosts["b"], hosts["c"]
        try:
            hot = _owned_key(a.core, 0)
            vip = _owned_key(a.core, 0, "vip")
            for _ in range(20):
                a.fwd.allow_n(hot, 1)
            a.fwd.set_override(vip, 11)
            a.persist.snapshot_now()
            a.kill()
            # B (a's successor) fails the range over.
            b.membership._dead.add("a")
            b.membership._maybe_failover(b.core.map.host("a"))
            assert b.core.map.epoch == 2
            assert not b.fwd.allow_n(hot, 1).allowed
            assert b.fwd.get_override(vip).limit == 11
            # Snapshot-age the successor: the aux unit must ride.
            entry = b.persist.snapshot_now()
            assert any(x["origin"] == "a" for x in entry.get("aux", []))
            # kill -9 the successor; C restores B's dir (own + aux).
            b.kill()
            unit = build_standby(c.cfg, b.dir, clock=clock)
            try:
                assert unit.get_override(vip).limit == 11
                assert not unit.allow_n(hot, 1).allowed
            finally:
                unit.close()
        finally:
            for h in (a, b, c):
                h.close()

    def test_release_removes_aux_from_snapshot_cycle(self, tmp_path):
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:
            a.persist.snapshot_now()
            a.kill()
            b.membership._dead.add("a")
            b.membership._maybe_failover(b.core.map.host("a"))
            assert any(x["origin"] == "a" for x in
                       b.persist.snapshot_now().get("aux", []))
            # A rejoins; after the give-back the aux entry stops.
            a2 = _Host("a", b.core.map, clock, tmp_path, hosts)
            hosts["a"] = a2
            b.membership.handle_announce(
                {"kind": "announce", "from": "a",
                 "map": a2.core.map.to_dict()})
            _rejoin_and_wait(b.membership, 3)
            assert not b.persist.snapshot_now().get("aux", [])
            a2.close()
        finally:
            for h in (a, b):
                h.close()


class TestMeshPeerStandby:
    def test_mesh_combined_snapshot_rebuckets_onto_standby(self,
                                                           tmp_path):
        """A sliced-mesh peer's combined snapshot cannot restore a
        single-unit standby directly; build_standby re-buckets it (the
        1-slice conservative union) instead of adopting fresh state —
        counters continue, overrides exact."""
        from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

        clock = ManualClock(1000.0)
        d = str(tmp_path / "mesh-peer")
        cfg = _cfg(d)
        pm = PersistenceManager(cfg.persistence)
        mesh = pm.wrap(SlicedMeshLimiter(cfg, clock, n_devices=4))
        cfg = mesh.config
        pm.attach([mesh])
        pm.recover()
        try:
            for _ in range(20):
                mesh.allow_n("hot", 1)
            mesh.set_override("vip", 3)
            pm.snapshot_now()
            unit = build_standby(cfg, d, clock=clock)
            try:
                assert not unit.allow_n("hot", 1).allowed
                assert unit.get_override("vip").limit == 3
            finally:
                unit.close()
        finally:
            pm.stop(final_snapshot=False)
            mesh.close()


class TestRejoin:
    def test_returning_host_takes_ranges_back_with_state(self, tmp_path):
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        try:
            hot = _owned_key(a.core, 0)
            vip = _owned_key(a.core, 0, "vip")
            for _ in range(12):
                a.fwd.allow_n(hot, 1)
            a.fwd.set_override(vip, 5)
            a.persist.snapshot_now()
            a.kill()
            b.membership._dead.add("a")
            b.membership._maybe_failover(b.core.map.host("a"))
            # B keeps charging the range while covering.
            for _ in range(8):
                b.fwd.allow_n(hot, 1)
            # A restarts fresh and announces; B hands the ranges back.
            a2 = _Host("a", b.core.map, clock, tmp_path, hosts)
            hosts["a"] = a2
            b.membership.handle_announce(
                {"kind": "announce", "from": "a",
                 "map": a2.core.map.to_dict()})
            assert "a" in b.membership._rejoin_pending
            _rejoin_and_wait(b.membership, 3)
            assert b.core.map.epoch == 3
            assert a2.core.map.epoch == 3
            assert a2.core.map.host("a").ranges == m.host("a").ranges
            assert b.core.status()["adopted_buckets"] == 0
            assert b.membership.rejoins == 1
            # A serves with the ACCUMULATED state (12 + 8 = at limit).
            assert not a2.fwd.allow_n(hot, 1).allowed
            assert a2.fwd.get_override(vip).limit == 5
            # Exactly one owner: B no longer serves the range locally.
            owners = a2.core.map.owner_table
            for lo, hi in m.host("a").ranges:
                assert (owners[lo:hi] == a2.core.map.ordinal("a")).all()
            a2.close()
        finally:
            for h in (a, b):
                h.close()

    def test_manual_rejoin_mode_never_hands_back(self, tmp_path):
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        b.membership.auto_rejoin = False
        try:
            a.persist.snapshot_now()
            a.kill()
            b.membership._dead.add("a")
            b.membership._maybe_failover(b.core.map.host("a"))
            b.membership.handle_announce(
                {"kind": "announce", "from": "a",
                 "map": a.core.map.to_dict()})
            assert "a" not in b.membership._rejoin_pending
            b.membership._maybe_rejoin()
            assert b.core.map.epoch == 2  # unchanged: operator's call
        finally:
            for h in (a, b):
                h.close()


class TestHandoffChaos:
    def test_kill_during_handoff_leaves_exactly_one_owner(self,
                                                          tmp_path):
        """Abort at EVERY injected phase: the flip is only ever
        published by the receiver after its restore, so a death at any
        point leaves the sender the single owner at the old epoch."""
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        inj = chaos_injector.install(seed=3)
        try:
            for phase in ("capture", "restore", "flip"):
                inj.abort_handoff(phase=phase, count=1)
                if phase == "capture":
                    # Sender-side abort surfaces to the caller.
                    with pytest.raises(chaos_injector.SliceFault):
                        a.membership.migrate_ranges(
                            m.host("a").ranges, "b", wait=0.2)
                else:
                    assert not a.membership.migrate_ranges(
                        m.host("a").ranges, "b", wait=0.2)
                assert a.core.map.epoch == 1
                assert b.core.map.epoch == 1
                assert a.core.map.host("a").ranges == m.host("a").ranges
                assert b.core.map.host("a").ranges == m.host("a").ranges
            assert inj.handoff_aborts == 3
            # Chaos cleared: the same move now completes.
            inj.clear()
            assert a.membership.migrate_ranges(m.host("a").ranges, "b",
                                               wait=2.0)
            assert b.core.map.epoch == 2
        finally:
            chaos_injector.uninstall()
            a.close()
            b.close()

    def test_migration_stall_keeps_old_owner_serving(self, tmp_path):
        clock = ManualClock(1000.0)
        m, hosts = _make_fleet(tmp_path, ["a", "b"], clock)
        a, b = hosts["a"], hosts["b"]
        inj = chaos_injector.install(seed=3)
        chaos_injector.scenario("migration-stall", inj, seconds=0.3)
        try:
            hot = _owned_key(a.core, 0)
            done = threading.Event()

            def move():
                a.membership.migrate_ranges(m.host("a").ranges, "b",
                                            wait=5.0)
                done.set()

            t = threading.Thread(target=move, daemon=True)
            t0 = time.monotonic()
            t.start()
            # During the stall the OLD owner still answers (epoch 1).
            time.sleep(0.1)
            assert a.core.map.epoch == 1
            assert a.fwd.allow_n(hot, 1).allowed
            assert done.wait(10.0)
            assert time.monotonic() - t0 >= 0.3
            assert inj.handoff_stalls == 1
            assert b.core.map.epoch == 2
        finally:
            chaos_injector.uninstall()
            a.close()
            b.close()

    def test_scenario_vocabulary_and_seeded_determinism(self):
        inj = chaos_injector.ChaosInjector(seed=9)
        for name in ("migration-stall", "kill-during-handoff",
                     "rejoin-storm"):
            chaos_injector.scenario(name, inj)
        with pytest.raises(ValueError):
            chaos_injector.scenario("no-such-scenario", inj)
        # rejoin-storm = seeded announce dropping: two injectors with
        # the same seed drop the SAME frame pattern (replay pin).
        frames = [bytes([13] * 20 + [i]) for i in range(64)]
        patterns = []
        for _ in range(2):
            x = chaos_injector.ChaosInjector(seed=21)
            chaos_injector.scenario("rejoin-storm", x)
            patterns.append([x.dcn_frame(f) is None for f in frames])
            assert any(patterns[-1]) and not all(patterns[-1])
        assert patterns[0] == patterns[1]


def _fleet_config(tmp_path, pa, pb, snap_a, snap_b):
    d = {"buckets": 32, "epoch": 1, "hosts": [
        {"id": "a", "host": "127.0.0.1", "port": pa,
         "ranges": [[0, 16]], "successor": "b", "snapshot_dir": snap_a},
        {"id": "b", "host": "127.0.0.1", "port": pb,
         "ranges": [[16, 32]], "successor": "a", "snapshot_dir": snap_b},
    ]}
    path = str(tmp_path / "fleet.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(d, f)
    return path, d


def _spawn_member(port, cfgpath, self_id, snap, extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # The suite's kill -9 tests can tear entries in the SHARED
    # persistent jit cache, and a handoff compiles new shapes
    # mid-serving — concurrent/torn cache reads abort XLA-CPU
    # (observed SIGSEGV/SIGABRT ~10%). Fleet members here compile
    # privately instead.
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    argv = [sys.executable, "-m", "ratelimiter_tpu.serving",
            "--backend", "sketch", "--limit", "100", "--window", "600",
            "--sketch-width", "8192", "--sub-windows", "6",
            "--port", str(port), "--no-prewarm", "--inflight", "8",
            "--fleet-config", cfgpath, "--fleet-self", self_id,
            "--fleet-forward-deadline", "60",
            "--fleet-heartbeat", "0.3", "--fleet-dead-after", "1.5",
            "--snapshot-dir", snap, "--snapshot-interval", "500",
            *extra]
    return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _wait_banner(proc, timeout=180):
    t0 = time.time()
    lines = []
    while time.time() - t0 < timeout:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        if line.startswith("serving"):
            return lines
    raise AssertionError("member never served:\n" + "".join(lines))


@pytest.mark.slow
class TestRollingRestartProcesses:
    def test_rolling_restart_zero_client_errors_and_rejoin(self,
                                                           tmp_path):
        """The satellite-4 drain contract over real processes: SIGTERM
        one member of a 2-host fleet under live FleetClient traffic
        with a deep --inflight window. The departure announce moves
        ownership BEFORE the socket closes, every outstanding request
        resolves, the member exits 0, no client request errors; the
        restarted member then gets its ranges back (auto rejoin)."""
        from ratelimiter_tpu.serving.client import FleetClient

        pa, pb = free_port(), free_port()
        snap_a = str(tmp_path / "sa")
        snap_b = str(tmp_path / "sb")
        cfgpath, fleet_d = _fleet_config(tmp_path, pa, pb, snap_a,
                                         snap_b)
        a = _spawn_member(pa, cfgpath, "a", snap_a)
        b = _spawn_member(pb, cfgpath, "b", snap_b)
        procs = [a, b]
        try:
            _wait_banner(a)
            _wait_banner(b)
            fc = FleetClient(fleet_d, call_timeout=120)
            errors = []
            counts = {"n": 0}
            stop = threading.Event()
            keys = [f"roll:{i}" for i in range(512)]

            def drive():
                i = 0
                while not stop.is_set():
                    frame = [keys[(i * 7 + j) % 512] for j in range(64)]
                    i += 1
                    try:
                        fc.allow_batch(frame)
                        counts["n"] += 64
                    except Exception as exc:  # noqa: BLE001 — counted
                        errors.append(repr(exc))

            t = threading.Thread(target=drive, daemon=True)
            t.start()
            time.sleep(2.0)
            # ---- rolling restart of member a
            a.send_signal(signal.SIGTERM)
            assert a.wait(timeout=120) == 0, "member a exited non-zero"
            time.sleep(1.0)
            served_during = counts["n"]
            assert served_during > 0
            # b owns everything after the departure announce.
            from ratelimiter_tpu.serving.client import Client

            with Client(port=pb, timeout=120) as cb:
                m_now = FleetMap.from_dict(cb.fleet_map())
            assert m_now.epoch >= 2
            assert m_now.owned_buckets("b") == 32, m_now.to_dict()
            # ---- member a returns; auto rejoin hands its ranges back
            a = _spawn_member(pa, cfgpath, "a", snap_a)
            procs[0] = a
            _wait_banner(a)
            deadline = time.time() + 60
            got_back = False
            while time.time() < deadline:
                with Client(port=pb, timeout=120) as cb:
                    m_now = FleetMap.from_dict(cb.fleet_map())
                if m_now.host("a").ranges:
                    got_back = True
                    break
                time.sleep(0.3)
            assert got_back, "rejoin never handed the ranges back"
            time.sleep(1.5)
            stop.set()
            t.join(timeout=30)
            fc.close()
            assert not errors, (
                f"{len(errors)} client error(s) during the rolling "
                f"restart; first: {errors[0]}")
            assert counts["n"] > served_during, \
                "no traffic served after the restart"
        finally:
            stop.set()
            for pr in procs:
                if pr.poll() is None:
                    pr.terminate()
            for pr in procs:
                try:
                    pr.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pr.kill()
