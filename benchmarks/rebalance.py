"""Load-aware placement benchmark (ADR-023): the ``rebalance`` block.

Measures the two halves of the rebalancing-brain story as NUMBERS
(``bench.py --rebalance`` -> REBALANCE_r01.json):

1. **convergence** — three real asyncio-door fleet members with a
   skewed hotspot (every probe bucket of member h0's range spent hot,
   its peers idle: measured imbalance >= 2.0x). The operator door
   (bearer-gated ``/v1/fleet/rebalance``) previews the plan with
   ``dry-run``, ``apply`` executes it over the real wire, and the block
   reports: imbalance before/after, the moves and the wall-clock apply
   window, the per-key admission oracle across the handoff (every
   pre-spent key admits EXACTLY limit tokens total — moved and kept
   alike; anything more is over-admission), client errors during the
   move (target: zero — the FleetClient self-heals over the redirect
   window), and the journal reconstruction (plan + move events under
   ONE correlation id via ``/debug/events?fleet=1``).
2. **off_pin** — rebalance machinery absent == byte-identical: the
   same workload through an in-process fleet routing stack (shared
   ManualClock) with and without the LoadSlab attached must produce
   the SAME decisions in the same order AND the same wire encoding of
   every result frame (sha256 over ``encode_result`` bytes).

Topology mirrors benchmarks/reshard.py: real server processes for the
wire half, the in-process stack for the determinism pin.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional

import numpy as np

from benchmarks.fleet import (
    REPO,
    _fleet_config_dict,
    _free_port,
    _wait_members,
)

TOKEN = "bench-rebalance"


def _spawn(port: int, http_port: int, cfgpath: str, self_id: str,
           snap: str) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    # limit 100 / window 600: the admission oracle needs counters that
    # outlive the whole EWMA-settle + apply + verify sequence.
    argv = [sys.executable, "-m", "ratelimiter_tpu.serving",
            "--backend", "sketch", "--limit", "100", "--window", "600",
            "--sketch-width", "8192", "--sub-windows", "6",
            "--max-batch", "4096", "--port", str(port),
            "--http-port", str(http_port),
            "--http-rebalance-token", TOKEN, "--debug-trace",
            # The automatic deployment shape; the long interval keeps
            # the measured cycle under the bench's control (the loop
            # sleeps a full interval before its first cycle, and
            # `apply` runs the IDENTICAL forced cycle).
            "--rebalance", "--rebalance-interval", "300",
            "--fleet-config", cfgpath, "--fleet-self", self_id,
            "--fleet-forward-deadline", "60",
            "--fleet-heartbeat", "0.3", "--fleet-dead-after", "2.0",
            "--snapshot-dir", snap, "--snapshot-interval", "500"]
    return subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)


def _verb(gateway: str, action: str) -> dict:
    base = f"{gateway}/v1/fleet/rebalance"
    if action == "status":
        url, method = base, "GET"
    else:
        url, method = f"{base}?action={action}", "POST"
    req = urllib.request.Request(
        url, method=method,
        headers={"Authorization": f"Bearer {TOKEN}"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read().decode())


class _ErrDriver:
    """Light background loadgen counting client-visible ERRORS (not
    denials) while the move is in flight."""

    def __init__(self, fleet: dict):
        from ratelimiter_tpu.serving.client import FleetClient

        self.fc = FleetClient(fleet, call_timeout=120)
        self.decisions = 0
        self.errors: List[str] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        i = 0
        while not self._stop.is_set():
            i += 1
            try:
                self.fc.allow_n(f"bg:{i % 200}", 1)
                self.decisions += 1
            except Exception as exc:  # noqa: BLE001 — the measurement
                self.errors.append(repr(exc))
            time.sleep(0.005)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30)
        self.fc.close()


def _run_convergence(*, log) -> Dict:
    import tempfile

    from ratelimiter_tpu.fleet import FleetMap
    from ratelimiter_tpu.ops.hashing import hash_prefixed_u64
    from ratelimiter_tpu.serving.client import Client, FleetClient

    buckets, n_hosts, limit, spend = 48, 3, 100, 60
    out: Dict = {
        "harness": (f"{n_hosts} asyncio-door fleet members, {buckets} "
                    f"buckets; one probe key per bucket of h0's range "
                    f"spent {spend}/{limit} hot (peers idle); operator "
                    "dry-run -> apply through the bearer door; "
                    "admission oracle + journal reconstruction after "
                    "the wire handoff"),
    }
    with tempfile.TemporaryDirectory() as tmp:
        ports = [_free_port() for _ in range(n_hosts)]
        https = [_free_port() for _ in range(n_hosts)]
        snaps = [os.path.join(tmp, f"snap-{i}") for i in range(n_hosts)]
        fleet = _fleet_config_dict(ports, buckets, snap_dirs=snaps,
                                   http_ports=https)
        cfgpath = os.path.join(tmp, "fleet.json")
        with open(cfgpath, "w", encoding="utf-8") as f:
            json.dump(fleet, f)
        members = [_spawn(ports[i], https[i], cfgpath, f"h{i}", snaps[i])
                   for i in range(n_hosts)]
        driver: Optional[_ErrDriver] = None
        try:
            _wait_members(members)
            gw = f"http://127.0.0.1:{https[0]}"
            out["auto"] = bool(_verb(gw, "status").get("auto"))

            # One probe key per bucket of h0's range [0, 16).
            prefix = "ratelimit"  # the server's default key prefix
            per = buckets // n_hosts
            keys: Dict[int, str] = {}
            for i in range(40000):
                k = f"rb:{i}"
                bkt = int(hash_prefixed_u64([k], prefix)[0] % buckets)
                if bkt < per and bkt not in keys:
                    keys[bkt] = k
                    if len(keys) == per:
                        break
            assert len(keys) == per
            probe = [keys[b] for b in sorted(keys)]
            t0 = time.perf_counter()
            with Client(port=ports[0], timeout=120) as c0:
                for _ in range(spend):
                    rs = c0.allow_batch(probe)
                    assert all(r.allowed for r in rs)
                    time.sleep(0.01)
            out["spend_s"] = round(time.perf_counter() - t0, 3)

            # Wait for the EWMA mass + peer liveness to settle into a
            # plan (each dry-run poll also triggers the load gather).
            t0 = time.perf_counter()
            plan = None
            deadline = time.time() + 90
            while time.time() < deadline:
                got = _verb(gw, "dry-run")
                if got.get("ok") and got["plan"]["moves"]:
                    plan = got["plan"]
                    break
                time.sleep(0.5)
            assert plan is not None, "dry-run never produced a plan"
            out["settle_s"] = round(time.perf_counter() - t0, 3)
            out["imbalance_before"] = plan["imbalance_before"]

            driver = _ErrDriver(fleet)
            driver.start()
            time.sleep(0.3)
            t0 = time.perf_counter()
            applied = _verb(gw, "apply")
            apply_s = time.perf_counter() - t0
            time.sleep(0.5)
            driver.stop()
            assert applied.get("ok"), applied
            moves = applied["plan"]["moves"][:applied["executed"]]
            out["apply"] = {
                "executed": applied["executed"],
                "planned": len(applied["plan"]["moves"]),
                "moves": [{"range": mv["range"], "from": mv["from"],
                           "to": mv["to"]} for mv in moves],
                "wall_s": round(apply_s, 3),
                "imbalance_projected":
                    applied["plan"]["imbalance_projected"],
                "plan_id": applied["plan"]["plan_id"],
            }
            out["client_errors_during_move"] = len(driver.errors)
            out["client_decisions_during_move"] = driver.decisions
            if driver.errors:
                out["first_error"] = driver.errors[0]

            # The new map really owns the moved ranges elsewhere.
            with Client(port=ports[1], timeout=120) as c1:
                m_now = FleetMap.from_dict(c1.fleet_map())
            out["epoch_final"] = m_now.epoch
            for mv in moves:
                lo, hi = mv["range"]
                assert (m_now.owner_table[lo:hi]
                        == m_now.ordinal(mv["to"])).all()

            # Measured imbalance AFTER: the same EWMA view re-summed
            # over the flipped ownership.
            after = _verb(gw, "dry-run")
            out["imbalance_after"] = (
                after["plan"]["imbalance_before"]
                if after.get("ok") and after.get("plan") else None)

            # Admission oracle: every pre-spent probe key — moved and
            # kept — admits exactly limit-spend more, then denies.
            moved_rs = [tuple(mv["range"]) for mv in moves]
            fc = FleetClient(fleet, call_timeout=120)
            oracle_errors = 0
            over = under = exact = 0
            try:
                for bkt, k in sorted(keys.items()):
                    more = 0
                    for _ in range(limit - spend + 5):
                        try:
                            more += bool(fc.allow_n(k, 1).allowed)
                        except Exception:  # noqa: BLE001 — count it
                            oracle_errors += 1
                    if more == limit - spend:
                        exact += 1
                    elif more > limit - spend:
                        over += 1
                    else:
                        under += 1
            finally:
                fc.close()
            moved_buckets = sum(hi - lo for lo, hi in moved_rs)
            out["oracle"] = {
                "keys": len(keys),
                "moved_buckets": moved_buckets,
                "exact": exact,
                "over_admitted_keys": over,
                "under_admitted_keys": under,
                "client_errors": oracle_errors,
            }

            # Journal reconstruction: plan + move events under ONE
            # correlation id through the fleet-merged door.
            with urllib.request.urlopen(
                    f"{gw}/debug/events?fleet=1&category=placement"
                    f"&limit=128", timeout=60) as r:
                evs = json.loads(r.read())["events"]
            plan_evs = [e for e in evs if e["action"] == "plan"]
            move_evs = [e for e in evs if e["action"] == "move"]
            corr = plan_evs[-1]["corr"] if plan_evs else None
            out["journal"] = {
                "plan_events": len(plan_evs),
                "move_events": len(move_evs),
                "corr": corr,
                "one_corr": bool(
                    corr and move_evs
                    and all(e["corr"] == corr for e in move_evs)),
            }
            out["pass"] = bool(
                out["imbalance_before"] >= 2.0
                and out["apply"]["executed"] >= 1
                and (out["imbalance_after"] or 99.0) <= 1.3
                and out["client_errors_during_move"] == 0
                and over == 0 and oracle_errors == 0
                and out["journal"]["one_corr"])
            log(f"rebalance convergence: imbalance "
                f"{out['imbalance_before']:.2f} -> "
                f"{out['imbalance_after']} "
                f"({out['apply']['executed']} moves, "
                f"{out['apply']['wall_s']}s), oracle exact={exact}/"
                f"{len(keys)} over={over}, errors="
                f"{out['client_errors_during_move']}+{oracle_errors}, "
                f"one_corr={out['journal']['one_corr']}")
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
    return out


def _run_off_pin(*, n_requests: int, log) -> Dict:
    """No rebalance machinery == byte-identical decisions AND wire
    frames, pinned over the in-process routing stack."""
    from ratelimiter_tpu import Algorithm, Config, SketchParams
    from ratelimiter_tpu.algorithms.sketch import SketchLimiter
    from ratelimiter_tpu.core.clock import ManualClock
    from ratelimiter_tpu.fleet import FleetCore, FleetForwarder, FleetMap
    from ratelimiter_tpu.fleet.config import FleetHost
    from ratelimiter_tpu.observability.metrics import Registry
    from ratelimiter_tpu.placement import LoadSlab
    from ratelimiter_tpu.serving import protocol

    def run(attach: bool):
        clock = ManualClock(1000.0)
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=50,
                     window=60.0,
                     sketch=SketchParams(depth=2, width=1024,
                                         sub_windows=6))
        lim = SketchLimiter(cfg, clock)
        m = FleetMap(buckets=48, hosts=(
            FleetHost(id="solo", host="127.0.0.1", port=1,
                      ranges=((0, 48),)),))
        m.validate()
        core = FleetCore(m, "solo", prefix=lim.config.prefix,
                         registry=Registry())
        if attach:
            core.load_slab = LoadSlab(48)
        fwd = FleetForwarder(lim, core)
        rng = np.random.default_rng(7)
        wire = hashlib.sha256()
        decisions = []
        try:
            for i in range(n_requests):
                k = f"pin:{int(rng.integers(0, 64))}"
                r = fwd.allow_n(k, int(rng.integers(1, 3)))
                decisions.append((k, bool(r.allowed), int(r.remaining),
                                  int(r.limit)))
                wire.update(protocol.encode_result(i & 0xFFFF, r))
                if i % 97 == 0:
                    clock.advance(0.5)
        finally:
            fwd.close()
            lim.close()
        return decisions, wire.hexdigest()

    plain, wire_plain = run(attach=False)
    slabbed, wire_slabbed = run(attach=True)
    identical = plain == slabbed and wire_plain == wire_slabbed
    log(f"rebalance off-pin: decisions_identical={plain == slabbed} "
        f"wire_identical={wire_plain == wire_slabbed} over "
        f"{n_requests} ops")
    return {"requests": n_requests,
            "decisions_identical": plain == slabbed,
            "wire_sha256": wire_plain,
            "wire_identical": wire_plain == wire_slabbed,
            "pass": identical}


def run_rebalance(*, seconds: float = 4.0, log=print) -> Dict:
    """The REBALANCE_r01 block."""
    del seconds  # the phases are event-driven, not time-driven
    return {
        "convergence": _run_convergence(log=log),
        "off_pin": _run_off_pin(n_requests=6000, log=log),
    }
