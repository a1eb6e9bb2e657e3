"""Elastic resharding + zero-downtime operations (ADR-018).

Two halves of the elastic lifecycle:

1. **re-bucketing** — a sliced-mesh snapshot taken at one device count
   restores onto ANOTHER (in-process here): clean splits copy state
   verbatim, merges take the conservative union, so overrides survive
   exactly and the resharded mesh never over-admits relative to its
   source. The same math runs offline as ``tools/rebucket.py``.
2. **zero-downtime rolling restart** — a two-member fleet (real server
   subprocesses) under live FleetClient traffic: SIGTERM one member and
   its departure handoff moves ownership to the survivor BEFORE the
   socket closes (no client errors); restart it and the automatic
   rejoin give-back returns its ranges, counters intact.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/16_elastic.py

Runbook: docs/OPERATIONS.md §10 (scale-out, scale-in, rolling restart).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")  # device backends need x64
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()


def part_one_rebucketing() -> None:
    import numpy as np

    from ratelimiter_tpu import Algorithm, Config, SketchParams
    from ratelimiter_tpu.checkpoint import save_state
    from ratelimiter_tpu.core.clock import ManualClock
    from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

    print("=== 1. re-bucketing: restore a 4-slice snapshot onto 3 "
          "slices ===")
    cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=20,
                 window=600.0,
                 sketch=SketchParams(depth=2, width=2048, sub_windows=6))
    clock = ManualClock(1000.0)
    src = SlicedMeshLimiter(cfg, clock, n_devices=4)
    cfg = src.config
    rng = np.random.default_rng(0)
    keys = [f"user:{i}" for i in range(40)]
    for _ in range(6):
        src.allow_batch([keys[j] for j in rng.integers(0, 40, size=48)]
                        + keys[:4])
        clock.advance(30.0)
    src.set_override("user:3", 5)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "mesh4.npz")
        kind, arrays, extra = src.capture_state()
        save_state(path, kind, cfg, arrays, extra)
        oracle = SlicedMeshLimiter(cfg, ManualClock(clock.now()),
                                   n_devices=4)
        oracle.restore(path)
        base = oracle.allow_batch(keys)
        for m in (3,):   # a prime count: every old slice contributes
            dst = SlicedMeshLimiter(cfg, ManualClock(clock.now()),
                                    n_devices=m)
            dst.restore(path)   # re-buckets instead of refusing
            out = dst.allow_batch(keys)
            over = int((out.allowed & ~base.allowed).sum())
            print(f"  4 -> {m} slices: override user:3 = "
                  f"{dst.get_override('user:3').limit}, "
                  f"allowed {int(out.allowed.sum())}/{len(keys)} "
                  f"(source {int(base.allowed.sum())}), "
                  f"over-admissions vs source = {over}")
            dst.close()
        oracle.close()
    src.close()


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def spawn(port, cfgpath, self_id, snap):
    env = dict(os.environ)
    # Private jit compiles: the shared persistent cache can hold torn
    # entries (kill -9 tests) and aborts XLA-CPU when the handoff
    # compiles new shapes mid-serving.
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    return subprocess.Popen(
        [sys.executable, "-m", "ratelimiter_tpu.serving",
         "--backend", "sketch", "--limit", "100", "--window", "600",
         "--sketch-width", "8192", "--sub-windows", "6",
         "--port", str(port), "--no-prewarm",
         "--snapshot-dir", snap, "--snapshot-interval", "500",
         "--fleet-config", cfgpath, "--fleet-self", self_id,
         "--fleet-forward-deadline", "60",
         "--fleet-heartbeat", "0.3", "--fleet-dead-after", "1.5"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def wait_banner(proc):
    while True:
        line = proc.stdout.readline()
        if not line:
            raise SystemExit("member died at start")
        if line.startswith("serving"):
            return


def fetch_map(port):
    from ratelimiter_tpu.fleet.config import FleetMap
    from ratelimiter_tpu.serving.client import Client

    with Client(port=port, timeout=60) as c:
        return FleetMap.from_dict(c.fleet_map())


def part_two_rolling_restart() -> None:
    from ratelimiter_tpu.serving.client import FleetClient

    print("=== 2. rolling restart: SIGTERM -> departure handoff -> "
          "restart -> rejoin ===")
    with tempfile.TemporaryDirectory() as tmp:
        pa, pb = free_port(), free_port()
        snaps = [os.path.join(tmp, "sa"), os.path.join(tmp, "sb")]
        fleet = {"buckets": 32, "epoch": 1, "hosts": [
            {"id": "a", "host": "127.0.0.1", "port": pa,
             "ranges": [[0, 16]], "successor": "b",
             "snapshot_dir": snaps[0]},
            {"id": "b", "host": "127.0.0.1", "port": pb,
             "ranges": [[16, 32]], "successor": "a",
             "snapshot_dir": snaps[1]}]}
        cfgpath = os.path.join(tmp, "fleet.json")
        with open(cfgpath, "w", encoding="utf-8") as f:
            json.dump(fleet, f)
        a = spawn(pa, cfgpath, "a", snaps[0])
        b = spawn(pb, cfgpath, "b", snaps[1])
        try:
            wait_banner(a)
            wait_banner(b)
            fc = FleetClient(fleet, call_timeout=60)
            served = errors = 0
            for i in range(20):
                try:
                    fc.allow_batch([f"k:{j}" for j in range(32)])
                    served += 32
                except Exception:  # noqa: BLE001
                    errors += 1
            print(f"  steady: served {served} decisions, {errors} "
                  f"errors")
            t0 = time.time()
            a.send_signal(signal.SIGTERM)
            rc = a.wait(timeout=120)
            m_now = fetch_map(pb)
            print(f"  SIGTERM a: exit code {rc}, map epoch "
                  f"{m_now.epoch}, b owns "
                  f"{m_now.owned_buckets('b')}/32 buckets "
                  f"({time.time() - t0:.1f}s)")
            for i in range(10):
                fc.allow_batch([f"k:{j}" for j in range(32)])
            print("  traffic kept flowing through b (forward/redirect "
                  "window)")
            a = spawn(pa, cfgpath, "a", snaps[0])
            wait_banner(a)
            t0 = time.time()
            while time.time() - t0 < 60:
                m_now = fetch_map(pb)
                if m_now.host("a").ranges:
                    break
                time.sleep(0.2)
            print(f"  restarted a: rejoin handed back "
                  f"{m_now.host('a').ranges} at epoch {m_now.epoch} "
                  f"({time.time() - t0:.1f}s)")
            fc.close()
        finally:
            for pr in (a, b):
                if pr.poll() is None:
                    pr.terminate()
            for pr in (a, b):
                try:
                    pr.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pr.kill()


if __name__ == "__main__":
    part_one_rebucketing()
    part_two_rolling_restart()
    print("elastic lifecycle OK")
