"""Elastic lifecycle benchmark (ADR-018): the ``reshard`` block.

Measures the three numbers the zero-downtime story promises, as
NUMBERS rather than assertions (``bench.py --reshard`` ->
RESHARD_r01.json):

* **migration window** — wall time from SIGTERM of one 2-host fleet
  member to the moment the survivor publishes the flipped epoch (the
  departure handoff: capture -> restore -> epoch bump);
* **rolling-restart retention** — client throughput during a full
  restart cycle of one member (SIGTERM -> depart -> exit -> restart ->
  auto rejoin) as a fraction of steady state, plus the client-visible
  error count (target: >= 0.9 retention, zero errors — the FleetClient
  self-heals over the forward/redirect window);
* **rejoin convergence** — wall time from the restarted member's
  serving banner until the survivor's handoff gives its ranges back
  (the map shows the returning host owning them again).

Also includes an offline row: ``tools/rebucket.py`` resize timings on a
grown mesh snapshot (the cold half of the elastic seam).

Topology mirrors benchmarks/fleet.py: two real asyncio-door sketch
members with snapshot dirs (the handoff artifact), driven by one
threaded FleetClient loadgen in this process — absolute rates are
GIL-capped, but retention is a ratio of like against like.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.fleet import (
    REPO,
    _fleet_config_dict,
    _free_port,
    _wait_members,
)


def _spawn(port: int, cfgpath: str, self_id: str, snap: str,
           seconds_hint: float) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["JAX_PLATFORMS"] = "cpu"
    # Private jit compiles: shared persistent-cache reads can abort
    # XLA-CPU when the handoff compiles new shapes mid-serving.
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    argv = [sys.executable, "-m", "ratelimiter_tpu.serving",
            "--backend", "sketch", "--limit", "1000000",
            "--window", "60", "--sketch-width", "16384",
            "--max-batch", "8192", "--inflight", "8",
            "--port", str(port),
            "--fleet-config", cfgpath, "--fleet-self", self_id,
            "--fleet-forward-deadline", "60",
            "--fleet-heartbeat", "0.25", "--fleet-dead-after", "1.5",
            "--snapshot-dir", snap, "--snapshot-interval", "500"]
    return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)


class _Driver:
    """Threaded FleetClient loadgen recording decisions + errors with
    timestamps, so any wall-clock window can be rated afterwards.

    ``pace`` (decisions/sec, settable live) switches from closed-loop
    saturation to a fixed OFFERED rate: retention through a restart
    then measures availability, not the halved fleet's capacity —
    the ISSUE-11 bar (>= 0.9 of steady state) is an availability
    number, so the offered rate must fit comfortably on one host."""

    def __init__(self, fleet: dict, frame: int = 1024):
        from ratelimiter_tpu.serving.client import FleetClient

        self.fc = FleetClient(fleet, call_timeout=120)
        self.frame = frame
        self.pace: Optional[float] = None
        self.events: List = []      # (t, decisions)
        self.errors: List = []      # (t, repr)
        self._stop = threading.Event()
        rng = np.random.default_rng(11)
        self.pool = rng.integers(0, 1 << 62, size=1 << 16,
                                 dtype=np.uint64)
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        k = 0
        next_t = time.perf_counter()
        while not self._stop.is_set():
            pace = self.pace
            if pace:
                now = time.perf_counter()
                next_t = max(next_t + self.frame / pace, now - 0.25)
                if next_t > now:
                    time.sleep(next_t - now)
            off = (k * 4099) % (self.pool.shape[0] - self.frame)
            k += 1
            try:
                self.fc.allow_hashed(self.pool[off:off + self.frame])
                self.events.append((time.perf_counter(), self.frame))
            except Exception as exc:  # noqa: BLE001 — the measurement
                self.errors.append((time.perf_counter(), repr(exc)))
        self.fc.close()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)

    def rate(self, t0: float, t1: float) -> float:
        n = sum(d for t, d in self.events if t0 <= t < t1)
        return n / max(t1 - t0, 1e-9)


def _fetch_map(port: int):
    from ratelimiter_tpu.fleet.config import FleetMap
    from ratelimiter_tpu.serving.client import Client

    with Client(port=port, timeout=60) as c:
        return FleetMap.from_dict(c.fleet_map())


def _offline_rebucket_row(tmp: str, log=print) -> Dict:
    """tools/rebucket.py timings on a grown combined snapshot."""
    from ratelimiter_tpu import Algorithm, Config, SketchParams
    from ratelimiter_tpu.checkpoint import save_state
    from ratelimiter_tpu.core.clock import ManualClock
    from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

    cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=1000,
                 window=60.0,
                 sketch=SketchParams(depth=4, width=65536,
                                     sub_windows=60))
    clock = ManualClock(1000.0)
    src = SlicedMeshLimiter(cfg, clock, n_devices=4)
    cfg = src.config
    rng = np.random.default_rng(0)
    for _ in range(4):
        src.allow_ids(rng.integers(0, 1 << 62, size=8192,
                                   dtype=np.uint64))
        clock.advance(0.5)
    kind, arrays, extra = src.capture_state()
    p4 = os.path.join(tmp, "mesh4.npz")
    save_state(p4, kind, cfg, arrays, extra)
    src.close()
    row: Dict = {"snapshot_bytes": os.path.getsize(p4),
                 "geometry": "4 slices, d=4 w=65536 sw=60"}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for target, label in ((8, "split_4_to_8"), (3, "merge_4_to_3")):
        out = os.path.join(tmp, f"mesh{target}.npz")
        t0 = time.perf_counter()
        subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "rebucket.py"),
             p4, out, "--slices", str(target)],
            check=True, env=env, stdout=subprocess.DEVNULL)
        row[f"{label}_s"] = round(time.perf_counter() - t0, 3)
    log(f"reshard offline: {row}")
    return row


def run_reshard(*, seconds: float = 4.0, warmup: float = 2.0,
                log=print) -> Dict:
    """The whole reshard block: steady state, rolling restart of one
    member (migration window + retention + errors), rejoin convergence,
    offline resize timings."""
    import tempfile

    out: Dict = {
        "harness": ("2 asyncio-door fleet members with snapshot dirs "
                    "(the handoff artifact); threaded FleetClient "
                    "loadgen; SIGTERM -> departure handoff -> restart "
                    "-> automatic rejoin give-back (ADR-018)"),
    }
    with tempfile.TemporaryDirectory() as tmp:
        ports = [_free_port(), _free_port()]
        snaps = [os.path.join(tmp, f"snap-{i}") for i in range(2)]
        fleet = _fleet_config_dict(ports, 32, snap_dirs=snaps)
        cfgpath = os.path.join(tmp, "fleet.json")
        with open(cfgpath, "w", encoding="utf-8") as f:
            json.dump(fleet, f)
        members = [_spawn(ports[i], cfgpath, f"h{i}", snaps[i], seconds)
                   for i in range(2)]
        driver: Optional[_Driver] = None
        try:
            _wait_members(members)
            driver = _Driver(fleet)
            driver.start()
            time.sleep(warmup)
            # Capacity probe (closed loop), then switch to a fixed
            # offered rate well inside ONE host's capacity so the
            # restart phase measures availability.
            t0 = time.perf_counter()
            time.sleep(max(1.5, seconds / 2))
            capacity = driver.rate(t0, time.perf_counter())
            driver.pace = max(1000.0, 0.35 * capacity)
            time.sleep(0.5)
            t0 = time.perf_counter()
            time.sleep(seconds)
            t1 = time.perf_counter()
            steady = driver.rate(t0, t1)
            out["capacity_decisions_per_sec"] = round(capacity, 1)
            out["offered_decisions_per_sec"] = round(driver.pace, 1)
            epoch0 = _fetch_map(ports[1]).epoch
            # ---- rolling restart of member 0
            t_term = time.perf_counter()
            members[0].send_signal(signal.SIGTERM)
            flip_at = None
            deadline = time.time() + 60
            while time.time() < deadline:
                try:
                    m_now = _fetch_map(ports[1])
                    if (m_now.epoch > epoch0
                            and m_now.owned_buckets("h1")
                            == fleet["buckets"]):
                        flip_at = time.perf_counter()
                        break
                except Exception:  # noqa: BLE001 — poll
                    pass
                time.sleep(0.02)
            rc = members[0].wait(timeout=120)
            t_exit = time.perf_counter()
            members[0] = _spawn(ports[0], cfgpath, "h0", snaps[0],
                                seconds)
            _wait_members([members[0]])
            t_back = time.perf_counter()
            rejoined_at = None
            deadline = time.time() + 90
            while time.time() < deadline:
                try:
                    m_now = _fetch_map(ports[1])
                    if m_now.host("h0").ranges:
                        rejoined_at = time.perf_counter()
                        break
                except Exception:  # noqa: BLE001 — poll
                    pass
                time.sleep(0.05)
            # Let routing settle before rating the post-rejoin phase:
            # the client learns the flip at its map_max_age cadence
            # (<= 3 s), and rating through that lag would charge the
            # forwarding hop to the rejoin.
            time.sleep(3.5)
            t_settle = time.perf_counter()
            time.sleep(max(1.5, seconds / 2))
            t_end = time.perf_counter()
            driver.stop()
            restart_rate = driver.rate(t_term, t_back)
            after_rate = driver.rate(t_settle, t_end)
            out["steady_decisions_per_sec"] = round(steady, 1)
            out["rolling_restart"] = {
                "migration_window_s": (round(flip_at - t_term, 3)
                                       if flip_at else None),
                "departed_member_exit_code": rc,
                "member_exit_s": round(t_exit - t_term, 3),
                "during_restart_decisions_per_sec": round(restart_rate,
                                                          1),
                "retention_vs_steady": (round(restart_rate / steady, 3)
                                        if steady else None),
                "client_errors": len(driver.errors),
                "first_error": (driver.errors[0][1]
                                if driver.errors else None),
            }
            out["rejoin"] = {
                "convergence_s": (round(rejoined_at - t_back, 3)
                                  if rejoined_at else None),
                "epoch_final": _fetch_map(ports[1]).epoch,
                "after_rejoin_decisions_per_sec": round(after_rate, 1),
            }
            log(f"reshard: steady={steady:.0f}/s "
                f"window={out['rolling_restart']['migration_window_s']}s "
                f"retention={out['rolling_restart']['retention_vs_steady']} "
                f"errors={out['rolling_restart']['client_errors']} "
                f"rejoin={out['rejoin']['convergence_s']}s")
        finally:
            if driver is not None and driver._thread.is_alive():
                driver.stop()
            for pr in members:
                if pr.poll() is None:
                    pr.terminate()
            for pr in members:
                try:
                    pr.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pr.kill()
        out["offline_rebucket"] = _offline_rebucket_row(tmp, log=log)
    return out
