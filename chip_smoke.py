#!/usr/bin/env python3
"""chip_smoke.py — the served path, end to end, on the chip.

Starts the real server (``python -m ratelimiter_tpu.serving --native``)
with the TPU as its device, once per leg, and drives it through the
entry points a user calls: the Python ``Client`` (scalar ``allow``, one
``allow_batch`` frame of string keys, one ``allow_hashed`` frame of u64
ids) and the C++ load generator on both lanes.

Legs (geometries are deployment size and are never cut):

  config3  BASELINE.json configs[2] literal: CMS d=4 w=65536, 60
           sub-windows, limit 100/60 s, default kernels and prewarm.
  wide     d=3 w=2^20 x 60 sub-windows, ~755 MB of HBM: the table does
           not fit VMEM, the state is resident at deployment size.
  bucket   the sketched token bucket (int64 debt slab) at config-3
           geometry.
  mesh     --backend mesh over every device the server reports, once per
           router (host, collective); asserts from the server's own
           report that each slice's state sits on its own device. On a
           one-chip machine it says one device and starts nothing.

Every leg is held to the answers ``--backend exact`` gives for the same
requests (the plain reference; it never imports JAX): K keys sent
limit+50 times each get exactly ``limit`` allowed with ``remaining``
counting down, and a fresh key is allowed. A leg in which any reply was
a fail-open/fail-closed policy answer or an error frame is a failed leg.

This process never imports JAX; the device is read from the server's
banner. One server child is alive at a time, and each is waited out
after SIGTERM before the next starts. Anything but a TPU is a failure.
``JAX_PLATFORMS=cpu`` set by the caller turns the run into a rehearsal
at tiny geometry: it says ``cpu``, prints no result line and exits 3.

Exit codes: 0 every leg passed on a TPU (last stdout line is the result
object); 1 a leg failed; 2 the server's device is not a TPU; 3 a CPU
rehearsal passed (no accelerator, so no result).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import select
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from ratelimiter_tpu.native import native_available
from ratelimiter_tpu.serving import Client
from ratelimiter_tpu.serving.native_server import native_server_available

REPO = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.environ.get("JAX_PLATFORMS") == "cpu"

LIMIT = 100
HOT_KEYS = 8            # K distinct keys, each sent LIMIT + 50 times
SENDS = LIMIT + 50
LEGS = ("config3", "wide", "bucket", "mesh")
BUDGET_S = 1170         # the contract allows 1200 s, compilation included

if REHEARSAL:
    CONFIG3, WIDE = (4, 2048, 60), (3, 8192, 60)
    FRAME, ID_SPACE, LOADGEN = 256, 10_000, ("1", "2", "2")
    SERVER_EXTRA = ["--max-batch", "256"]   # 7 pad shapes to compile, not 11
else:
    CONFIG3, WIDE = (4, 65536, 60), (3, 1 << 20, 60)
    FRAME, ID_SPACE, LOADGEN = 4096, 1_000_000, ("5", "4", "4")
    SERVER_EXTRA = []
# LOADGEN = (seconds per lane, threads, frames in flight per thread)

_BANNER = re.compile(
    r"device=(?P<platform>\w+)/(?P<kind>.+?) x(?P<count>\d+) "
    r"kernels=(?P<kernels>\w+) slice_devices=(?P<slices>[\d,+]+)")
_PREWARM = re.compile(r"prewarmed pad shapes .* in ([\d.]+)s")
_POLICY = re.compile(
    r'^rate_limiter_(?:requests_total\{.*result="fail_open".*\}'
    r"|server_slo_breach_decisions_total|breaker_short_circuits_total\S*)"
    r" (\S+)$", re.M)
_ERRORS = re.compile(
    r'^rate_limiter_(?:requests_total\{.*result="error:[^"]*".*\}'
    r"|storage_errors_total\S*) (\S+)$", re.M)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Server:
    """What a running ``python -m ratelimiter_tpu.serving`` child told
    us: its banner, its port, and how long it took to get there."""

    def __init__(self, err, banner: str, setup_s: float):
        self._err, self.banner, self.setup_s = err, banner, setup_s
        self.port = int(re.search(r" on \S+:(\d+) ", banner).group(1))

    def stderr_text(self) -> str:
        self._err.seek(0)
        return self._err.read().decode(errors="replace")


def _read_banner(proc, cmd: list) -> str:
    """The stdout line starting with ``serving`` (printed after prewarm),
    read without blocking past the child's death."""
    fd = proc.stdout.fileno()
    buf = b""
    while True:
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if line.startswith(b"serving"):
                return line.decode()
        ready, _, _ = select.select([fd], [], [], 1.0)
        chunk = os.read(fd, 65536) if ready else None
        if chunk == b"" or (chunk is None and proc.poll() is not None):
            raise SmokeFailure(
                f"server exited with code {proc.wait()} before its "
                f"banner: {' '.join(cmd)}")
        buf += chunk or b""


@contextlib.contextmanager
def serving(flags: list):
    """Start one server child and yield it once its banner is out. On
    the way out SIGTERM it and wait for its exit — the next leg's server
    needs the chip this one holds. Its stderr is kept in a file and
    printed whenever the block, or the shutdown, fails."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    cmd = [sys.executable, "-m", "ratelimiter_tpu.serving", "--native",
           "--port", "0", "--limit", str(LIMIT)] + flags
    with tempfile.TemporaryFile() as err:
        t0 = time.monotonic()
        proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                                stdout=subprocess.PIPE, stderr=err)
        passed = False
        try:
            banner = _read_banner(proc, cmd)
            yield Server(err, banner, round(time.monotonic() - t0, 1))
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
            check(proc.wait(timeout=90) == 0,
                  f"server exited with code {proc.returncode} on SIGTERM")
            passed = True
        finally:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            proc.stdout.close()
            if not passed:
                err.seek(0)
                sys.stderr.write(
                    f"--- stderr of {' '.join(cmd)}\n"
                    f"{err.read().decode(errors='replace')[-8000:]}\n"
                    f"--- end stderr\n")


# ------------------------------------------------------------ the requests

def _hot_schedule(lane: str) -> list:
    """How many times each hot key appears in each batch frame: one big
    frame (in-batch duplicates, padded to FRAME with filler keys), then
    frames of 7 per key, so the limit is crossed mid-frame. With the
    string lane's two scalar sends per key it sums to SENDS."""
    scalar = 2 if lane == "string" else 0
    big = min(60, FRAME // (2 * HOT_KEYS)) - scalar
    left = SENDS - scalar - big
    return [big] + [7] * (left // 7) + ([left % 7] if left % 7 else [])


def drive_lane(client: Client, lane: str, as_strings: bool = False) -> dict:
    """One lane's requests and replies, in order: {"hot", "keys",
    "allowed", "remaining"}, ending with one never-seen key. ``lane`` is "string" (scalar allow,
    then allow_batch) or "hashed" (allow_hashed; the exact reference has
    no hashed lane and takes the same requests as string keys,
    ``as_strings``)."""
    rng = np.random.default_rng(21)
    if lane == "string":
        hot = [f"smoke:hot:{i}" for i in range(HOT_KEYS)]
        filler = [f"smoke:filler:{i}" for i in range(FRAME)]
        fresh = "smoke:fresh"
    else:
        base = 1 << 40
        hot = [base + i for i in range(HOT_KEYS)]
        filler = [base + (1 << 20) + i for i in range(FRAME)]
        fresh = base + (1 << 30)
    out = {"hot": hot, "keys": [], "allowed": [], "remaining": []}
    fail_open = False

    def note(key, allowed, remaining) -> None:
        out["keys"].append(key)
        out["allowed"].append(bool(allowed))
        out["remaining"].append(int(remaining))

    def send(keys: list) -> None:
        nonlocal fail_open
        if lane == "hashed" and not as_strings:
            res = client.allow_hashed(np.asarray(keys, np.uint64))
            fail_open |= bool(res.fail_open)
            for k, a, r in zip(keys, res.allowed, res.remaining):
                note(k, a, r)
            return
        wire = [f"id:{k}" for k in keys] if lane == "hashed" else keys
        for k, r in zip(keys, client.allow_batch(wire)):
            fail_open |= r.fail_open
            note(k, r.allowed, r.remaining)

    if lane == "string":
        for key in hot * 2:
            r = client.allow(key)
            fail_open |= r.fail_open
            note(key, r.allowed, r.remaining)
    for i, reps in enumerate(_hot_schedule(lane)):
        keys = hot * reps
        if i == 0:
            keys = keys + filler[:FRAME - len(keys)]
        send([keys[j] for j in rng.permutation(len(keys))])
    send([fresh])
    check(not fail_open, f"{lane} lane: a reply carried the fail_open flag")
    return out


def check_rule(who: str, lane: str, got: dict) -> None:
    """Per hot key, in request order: the first LIMIT sends are allowed
    with ``remaining`` counting LIMIT-1 .. 0, the other 50 are denied;
    the fresh key is allowed."""
    for key in got["hot"]:
        at = [i for i, k in enumerate(got["keys"]) if k == key]
        check(len(at) == SENDS, f"{who} {lane}: {key} sent {len(at)} times")
        allowed = [got["allowed"][i] for i in at]
        check(allowed == [True] * LIMIT + [False] * (SENDS - LIMIT),
              f"{who} {lane}: {key} allowed {sum(allowed)} of {SENDS} "
              f"(want exactly the first {LIMIT})")
        check([got["remaining"][i] for i in at[:LIMIT]]
              == list(range(LIMIT - 1, -1, -1)),
              f"{who} {lane}: remaining of {key} does not count down")
    check(got["allowed"][-1] and got["remaining"][-1] == LIMIT - 1,
          f"{who} {lane}: the fresh key was not allowed with "
          f"{LIMIT - 1} left")


def check_lane(lane: str, got: dict, want: dict) -> None:
    """The served answers obey the rule and equal the exact backend's."""
    check_rule("served", lane, got)
    check(got["keys"] == want["keys"], f"{lane}: request streams differ")
    bad = [i for i in range(len(want["keys"]))
           if (got["allowed"][i], got["remaining"][i])
           != (want["allowed"][i], want["remaining"][i])]
    check(not bad,
          f"{lane}: {len(bad)} of {len(want['keys'])} replies differ from "
          f"the exact backend's; request {bad[:1]} got "
          f"{[(got['allowed'][i], got['remaining'][i]) for i in bad[:1]]}"
          f", want "
          f"{[(want['allowed'][i], want['remaining'][i]) for i in bad[:1]]}")


def reference(algorithm: str, window: int) -> dict:
    """The exact backend's answers to both lanes' requests."""
    if algorithm != "token_bucket":
        # The exact sliding window is the reference's two-window
        # estimate on an epoch-aligned grid; a run across a grid line
        # admits more than LIMIT. Stay clear of the line.
        to_line = window - time.time() % window
        if to_line < 12:
            time.sleep(to_line + 0.2)
    with serving(["--backend", "exact", "--algorithm", algorithm,
                  "--window", str(window)]) as srv:
        check("device=host" in srv.banner, f"reference banner: {srv.banner}")
        with Client(port=srv.port, timeout=60) as c:
            want = {"string": drive_lane(c, "string"),
                    "hashed": drive_lane(c, "hashed", as_strings=True)}
    for lane, answers in want.items():
        check_rule("exact", lane, answers)
    return want


# ---------------------------------------------------------------- the legs

def run_loadgen(binary: str, port: int, mode: str) -> dict:
    seconds, threads, inflight = LOADGEN
    out = subprocess.run(
        [binary, "127.0.0.1", str(port), seconds, threads, inflight,
         str(FRAME), str(ID_SPACE), mode],
        capture_output=True, text=True, timeout=float(seconds) + 120)
    check(out.returncode == 0, f"loadgen {mode} exited {out.returncode}: "
                               f"{out.stderr[-2000:]}")
    row = json.loads(out.stdout.strip().splitlines()[-1])
    check(row["completed"] > 0, f"loadgen {mode}: no decision completed")
    check(row["error_frames"] == 0 and row["fail_open"] == 0,
          f"loadgen {mode}: {row['error_frames']} error frames, "
          f"{row['fail_open']} policy-answered decisions")
    # Counts always; a rate or a latency only from a chip run, and
    # nothing is asserted on either.
    keep = ("completed", "allowed") if REHEARSAL else (
        "completed", "allowed", "decisions_per_sec", "frame_p50_ms",
        "frame_p99_ms")
    return {k: row[k] for k in keep}


def run_leg(name: str, flags: list, geometry: tuple, want: dict,
            binary: str, mesh: bool = False) -> dict:
    d, w, sw = geometry
    with serving(flags + ["--sketch-depth", str(d), "--sketch-width", str(w),
                         "--sub-windows", str(sw)] + SERVER_EXTRA) as srv:
        m = _BANNER.search(srv.banner)
        check(m is not None, f"banner names no device: {srv.banner}")
        platform, count = m["platform"], int(m["count"])
        if platform != "tpu" and not (REHEARSAL and platform == "cpu"):
            sys.stderr.write(
                f"chip_smoke: the server's device is {platform}/{m['kind']}"
                f", not a TPU (set JAX_PLATFORMS=cpu for a rehearsal)\n")
            raise SystemExit(2)
        slices = m["slices"].split(",")
        if mesh:
            # One slice per device, each slice's state on its own device
            # and on no other ("a+b" = one slice's leaves on two).
            check(sorted(slices) == sorted(str(i) for i in range(count)),
                  f"mesh over x{count} devices, but the server reports "
                  f"slice state on devices {slices}")
        with Client(port=srv.port, timeout=120) as c:
            got = {lane: drive_lane(c, lane) for lane in ("string", "hashed")}
            for lane in got:
                check_lane(lane, got[lane], want[lane])
            loadgen = {mode: run_loadgen(binary, srv.port, mode)
                       for mode in ("batch", "hashed")}
            text = c.metrics()
            decisions = c.health()[2]
        policy = sum(float(v) for v in _POLICY.findall(text))
        errors = sum(float(v) for v in _ERRORS.findall(text))
        check(policy == 0 and errors == 0,
              f"{policy:g} decisions answered by policy, {errors:g} "
              f"dispatch errors (server metrics)")
        sent = sum(len(g["allowed"]) for g in got.values())
        check(decisions >= sent + sum(r["completed"]
                                      for r in loadgen.values()),
              f"server counted {decisions} decisions, fewer than sent")
        prewarm = [float(s) for s in _PREWARM.findall(srv.stderr_text())]
        leg = {
            "leg": name, "platform": platform, "device_kind": m["kind"],
            "devices": count, "kernels": m["kernels"],
            "slice_devices": slices,
            "geometry": {"depth": d, "width": w, "sub_windows": sw},
            "setup_s": srv.setup_s, "prewarm_s": round(sum(prewarm), 1),
            "decisions": decisions,
            "allowed_per_key": sorted({
                sum(a for k, a in zip(g["keys"], g["allowed"]) if k == hot)
                for g in got.values() for hot in g["hot"]}),
            "expected_per_key": LIMIT,
            "policy_answered": int(policy), "dispatch_errors": int(errors),
            "loadgen": loadgen,
        }
    print(json.dumps(leg), flush=True)
    return leg


def build_loadgen(td: str) -> str:
    binary = os.path.join(td, "rltpu_loadgen")
    out = subprocess.run(
        ["g++", "-O2", "-std=c++17",
         os.path.join(REPO, "clients", "cpp", "loadgen.cpp"),
         "-o", binary, "-pthread"],
        capture_output=True, text=True, timeout=300)
    check(out.returncode == 0, f"loadgen.cpp did not build:\n{out.stderr}")
    return binary


def _on_alarm(signum, frame):
    raise SmokeFailure(f"the smoke did not finish in {BUDGET_S} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of %(default)s (the "
                         "four-chip run spends its budget on 'mesh')")
    legs = [leg for leg in ap.parse_args().legs.split(",") if leg]
    check(legs and set(legs) <= set(LEGS), f"--legs takes {LEGS}")
    t_start = time.monotonic()
    signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(BUDGET_S)
    # Built from this checkout's sources before any server starts; a
    # compiler refusal surfaces here with its message.
    check(shutil.which("g++") is not None, "no g++ on this host")
    check(native_available() and native_server_available(),
          "the native extensions did not build")
    sketch = ["--backend", "sketch", "--algorithm", "tpu_sketch",
              "--window", "60"]
    done = []
    with tempfile.TemporaryDirectory() as td:
        binary = build_loadgen(td)
        windowed = reference("sliding_window", 60)
        if "config3" in legs:
            done.append(run_leg("config3", sketch, CONFIG3, windowed,
                                binary))
        if "wide" in legs:
            done.append(run_leg("wide", sketch, WIDE, windowed, binary))
        if "bucket" in legs:
            # One token per 36 s: the burst sees exactly LIMIT tokens.
            done.append(run_leg(
                "bucket", ["--backend", "sketch", "--algorithm",
                           "token_bucket", "--window", "3600"],
                CONFIG3, reference("token_bucket", 3600), binary))
        if "mesh" in legs and done and done[0]["devices"] == 1:
            print(json.dumps({
                "leg": "mesh", "ran": False, "devices": 1,
                "platform": done[0]["platform"],
                "note": "the server reports one device: no mesh was "
                        "started, and this run says nothing about four"}),
                flush=True)
        elif "mesh" in legs:
            for router in ("host", "collective"):
                done.append(run_leg(
                    f"mesh-{router}",
                    ["--backend", "mesh", "--router", router,
                     "--algorithm", "tpu_sketch", "--window", "60"],
                    CONFIG3, windowed, binary, mesh=True))
    signal.alarm(0)
    device = {"platform": done[0]["platform"],
              "kind": done[0]["device_kind"], "count": done[0]["devices"]}
    summary = {"legs": [leg["leg"] for leg in done],
               "setup_s": {leg["leg"]: leg["setup_s"] for leg in done},
               "wall_s": round(time.monotonic() - t_start, 1)}
    if REHEARSAL:
        print(json.dumps({"rehearsal": True, "device": device, **summary}))
        print(f"rehearsal on {device['platform']}: {len(done)} legs passed; "
              f"no accelerator, so no result")
        return 3
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
