"""One run of one cell: ``python -m chipbench --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

The runner never imports JAX. It builds the load generator once per
checkout, starts ONE server child (chipbench/serve_child.py: the
program's ``python -m ratelimiter_tpu.serving --port 0 <server_flags>``
unchanged, with the chip as its device), reads the device from the server's banner and
refuses anything but a TPU, probes correctness, warms up with the cell's
own traffic, measures, checks again, stops the child and prints the
result object as the last line of stdout. The generator is spawned
BEFORE the server and builds its tables beside the server's start; once
it has said ``ready`` and the probe is done it is given the port and the
instant its warm-up starts.

``JAX_PLATFORMS=cpu`` set by the caller makes the run a rehearsal: the
configuration's and the mix's ``rehearsal`` overrides (a tiny geometry),
counts and no rate, no result line, exit code 3.

Exit codes: 0 a result line was printed; 1 the run broke; 2 no TPU (or
fewer chips than the cell asks for), or not a checkout of the program;
3 a rehearsal passed.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import math
import os
import re
import select
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time
import urllib.request

from chipbench import bytes as need
from chipbench import layers, probe, promtext
from chipbench.layers import prewarm_s
from chipbench.wire import Wire

T_SPAWN = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL = os.environ.get("JAX_PLATFORMS") == "cpu"
PROFILE_S = 1.0 if REHEARSAL else 5.0   # the traced stretch of the window
TRACED_MIN_S = 16.0        # a traced window holds profiler start-up + 5 s
WARMUP_S = 3.0             # the cell's own traffic, counted as set-up
DRAIN_S = 2.0              # open loop: a frame unanswered by then has failed
START_LEAD_S = 0.3         # from the instant given to the start of warm-up
#: The generator's tables (alias table, permutation) take 1.6 s at 20 M
#: keys and ~9 s at 10^8 on a core of the chip's host: one that has not
#: said ``ready`` this long after its spawn never will. A line said in
#: time is taken whenever ``start`` comes to read it (the server's cold
#: start between spawn and ``start`` is longer than this).
READY_CEILING_S = 120.0
#: /debug/profile answers 200 when the capture starts and then a space
#: every 5 s until its body is ready (``jax.profiler.stop_trace()`` costs
#: ~25 ms a captured program execution). A capture is waited for while
#: those bytes keep coming: silent for this long, the server is taken
#: for dead ...
HEARTBEAT_GAP_S = 30.0
#: ... and however alive, no longer than this: 4 x the largest capture
#: on record (104.7 s, mesh4-coll-mixed, PR 31).
CAPTURE_CEILING_S = 420.0

#: Every field of a traffic file, with its default.
TRAFFIC_DEFAULTS = {
    "lane": "hashed",          # hashed (ALLOW_HASHED u64 ids) | string (ALLOW_BATCH "user:<id>")
    "frame_keys": 4096,        # decisions per frame
    "connections": 4,
    "loop": "closed",          # closed | open
    "inflight": 4,             # closed loop: frames in flight per connection
    "rate": None,              # open loop: decisions per second, all connections
    "arrival": "poisson",      # poisson | uniform
    "zipf_s": 1.1,             # 0 = uniform
    "cost_n": 1,               # the n of every decision (a cost MIX is not implemented)
    "burst_on_s": 0.0,         # on/off bursts: not implemented, must stay 0
    "burst_off_s": 0.0,
    "affine_spread": 0,        # slice-affine connections: not implemented, must stay 0
    "rehearsal": {},           # overrides under JAX_PLATFORMS=cpu
}
_NOT_IMPLEMENTED = ("burst_on_s", "burst_off_s", "affine_spread")

_BANNER = re.compile(
    r"device=(?P<platform>\w+)/(?P<kind>.+?) x(?P<count>\d+) "
    r"kernels=(?P<kernels>\w+) slice_devices=(?P<slices>[\d,+]+)")
_PORT = re.compile(r" on \S+:(\d+) ")
_HTTP = re.compile(r" http:(\d+)")


class RunFailure(Exception):
    """The run cannot give a result."""


class NoChip(RunFailure):
    """The server's device is not what the cell asks for."""


def say(kind: str, **fields) -> None:
    """An earlier line of stdout: one JSON object, ``kind`` first."""
    print(json.dumps({"line": kind, **fields}), flush=True)


# ------------------------------------------------------------ the manifest

def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell as data: its entry of BENCHMARK.json, its configuration
    file and its traffic file (defaults filled in, rehearsal overrides
    applied under JAX_PLATFORMS=cpu)."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise RunFailure(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    entry = cells[workload]
    cfg_file = {c["name"]: c["file"] for c in manifest["configs"]}[entry["config"]]
    with open(os.path.join(root, cfg_file)) as fh:
        config = json.load(fh)
    with open(os.path.join(root, "chipbench", "traffic",
                           entry["traffic"] + ".json")) as fh:
        given = json.load(fh)
    unknown = set(given) - set(TRAFFIC_DEFAULTS) - {"why"}
    if unknown:
        raise RunFailure(f"traffic file {entry['traffic']}: unknown fields "
                         f"{sorted(unknown)}")
    traffic = {**TRAFFIC_DEFAULTS, **given}
    if REHEARSAL:
        traffic.update(traffic["rehearsal"])
        config = {**config, **config.get("rehearsal", {})}
    for field in _NOT_IMPLEMENTED:
        if traffic[field]:
            raise RunFailure(f"traffic field {field!r} is not implemented by "
                             f"chipbench/loadgen yet")
    if traffic["loop"] == "open" and not traffic["rate"]:
        raise RunFailure("an open-loop mix needs a rate")
    try:
        need.model_of(config)
    except need.NoByteModel as exc:
        raise RunFailure(f"configuration {entry['config']}: {exc}") from exc
    return {"name": workload, "chips": entry["chips"], "config": config,
            "traffic": traffic, "config_name": entry["config"],
            "traffic_name": entry["traffic"], "manifest": manifest}


def cell_metrics(cell: dict, group: str) -> list:
    """The manifest's metrics of ``group`` that this cell reports."""
    return [m for m in cell["manifest"][group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


# ----------------------------------------------------------- the generator

def build_loadgen(root: str = ROOT) -> tuple:
    """(binary, seconds spent building): chipbench/.build/<sha256 of the
    sources>/loadgen, compiled once per checkout."""
    src_dir = os.path.join(root, "chipbench", "loadgen")
    sources = sorted(f for f in os.listdir(src_dir)
                     if f.endswith((".cpp", ".hpp")))
    digest = hashlib.sha256()
    for name in sources:
        with open(os.path.join(src_dir, name), "rb") as fh:
            digest.update(name.encode() + b"\0" + fh.read() + b"\0")
    out_dir = os.path.join(root, "chipbench", ".build", digest.hexdigest())
    binary = os.path.join(out_dir, "loadgen")
    if os.path.exists(binary):
        return binary, 0.0
    if shutil.which("g++") is None:
        raise RunFailure("no g++ on this host")
    t0 = time.monotonic()
    os.makedirs(out_dir, exist_ok=True)
    tmp = f"{binary}.{os.getpid()}.tmp"
    done = subprocess.run(
        ["g++", "-O2", "-std=c++17", os.path.join(src_dir, "loadgen.cpp"),
         "-o", tmp, "-pthread"], capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        raise RunFailure(f"loadgen.cpp did not build:\n{done.stderr}")
    os.replace(tmp, binary)
    return binary, time.monotonic() - t0


def loadgen_args(cell: dict, port: int | None, seed: int, seconds: float,
                 start_at: float | None = None) -> list:
    """The generator's options for ``cell``. Without ``start_at`` it is
    told to build, say ``ready`` and wait for its schedule and the port on
    its stdin (`Generator`; ``port`` is None); with one (a hand run) it
    takes ``port`` and that instant, and exits 4 where its tables were not
    ready by the end of the warm-up."""
    assert (port is None) == (start_at is None)
    t, cfg = cell["traffic"], cell["config"]
    args = {
        "seed": seed, "lane": t["lane"],
        "frame-keys": t["frame_keys"], "conns": t["connections"],
        "loop": t["loop"], "inflight": t["inflight"],
        "arrival": t["arrival"], "zipf-s": t["zipf_s"],
        "keys": cfg["key_population"], "cost-n": t["cost_n"],
        "slices": cell["chips"],
        "warmup": WARMUP_S, "seconds": seconds, "drain": DRAIN_S,
    }
    if start_at is None:
        args["await-start"] = 1
    else:
        args["port"], args["start-at"] = port, f"{start_at:.6f}"
    if t["loop"] == "open":
        args["rate"] = t["rate"]
    return [x for k, v in args.items() for x in (f"--{k}", str(v))]


class Generator:
    """One generator process on a schedule it is GIVEN once its tables
    are built. Spawning returns at once and needs no port: the build
    runs beside whatever the caller does next (the server's start).
    ``start`` waits for its ``ready`` line, gives it the server's port
    and the instant warm-up starts (CLOCK_MONOTONIC, ``START_LEAD_S``
    from now) and returns the schedule in the generator's own words;
    ``result`` waits for its exit and returns its last line."""

    def __init__(self, binary: str, cell: dict, seed: int, seconds: float):
        self.keys = cell["config"]["key_population"]
        self.seconds = seconds
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [binary] + loadgen_args(cell, None, seed, seconds),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)
        self._buf = b""

    def _line(self, kind: str, deadline: float) -> dict:
        """The next stdout line, which must be a ``kind`` line."""
        fd = self.proc.stdout.fileno()
        while b"\n" not in self._buf:
            # What is already in the pipe is read whatever the clock says:
            # the ceiling is on the BUILD, and `start` is called a server's
            # start (a cold compile: 140-630 s) after the spawn, long after
            # a line that was written in time.
            left = max(deadline - time.monotonic(), 0.0)
            if not select.select([fd], [], [], left)[0]:
                self.stop()
                raise RunFailure(
                    f"the load generator has not said {kind!r} "
                    f"{time.monotonic() - self.t_spawn:.0f} s after its "
                    f"spawn, building its tables for {self.keys} keys "
                    f"(ceiling {READY_CEILING_S:g} s)")
            chunk = os.read(fd, 65536)
            if not chunk:                 # it has exited: say why
                _, err = self.proc.communicate()
                raise RunFailure(
                    f"the load generator exited {self.proc.returncode} "
                    f"before its {kind!r} line ({self.keys} keys): "
                    f"{err.decode(errors='replace')[-2000:]}")
            self._buf += chunk
        line, self._buf = self._buf.split(b"\n", 1)
        said = json.loads(line)
        if said.get("line") != kind:
            self.stop()
            raise RunFailure(f"the load generator said {said!r} where its "
                             f"{kind!r} line was due")
        return said

    def start(self, port: int) -> dict:
        self._line("ready", self.t_spawn + READY_CEILING_S)
        self.proc.stdin.write(
            f"{time.monotonic() + START_LEAD_S:.6f} {port}\n".encode())
        self.proc.stdin.flush()
        return self._line("schedule", time.monotonic() + 10.0)

    def result(self) -> dict:
        try:
            out, err = self.proc.communicate(
                timeout=WARMUP_S + self.seconds + DRAIN_S + 60)
        finally:
            self.stop()
        if self.proc.returncode != 0:
            raise RunFailure(
                f"the load generator exited {self.proc.returncode}: "
                f"{err.decode(errors='replace')[-2000:]}")
        return json.loads((self._buf + out).strip().splitlines()[-1])

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


# -------------------------------------------------------------- the server

class Server:
    def __init__(self, proc, banner: str, log_path: str, report_path: str):
        self.proc, self.banner = proc, banner
        self.log_path, self.report_path = log_path, report_path
        m = _BANNER.search(banner)
        if m is None:
            raise RunFailure(f"the banner names no device: {banner}")
        self.device = {"platform": m["platform"], "kind": m["kind"],
                       "count": int(m["count"])}
        self.kernels, self.slices = m["kernels"], m["slices"].split(",")
        self.port = int(_PORT.search(banner).group(1))
        http = _HTTP.search(banner)
        self.http_port = int(http.group(1)) if http else None

    def log(self) -> str:
        with open(self.log_path, errors="replace") as fh:
            return fh.read()


def _read_banner(proc) -> str:
    """The stdout line starting with ``serving`` (printed after prewarm),
    read without blocking past the child's death."""
    fd, buf = proc.stdout.fileno(), b""
    while True:
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if line.startswith(b"serving"):
                return line.decode()
        ready, _, _ = select.select([fd], [], [], 1.0)
        chunk = os.read(fd, 65536) if ready else None
        if chunk == b"" or (chunk is None and proc.poll() is not None):
            raise RunFailure(f"the server exited with code {proc.wait()} "
                             f"before its banner")
        buf += chunk or b""


@contextlib.contextmanager
def serving(cell: dict, out_dir: str, trace: bool):
    """Start the one server child; yield it once its banner is out; on
    the way out SIGTERM it and wait for its exit."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    if REHEARSAL and cell["chips"] > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={cell['chips']}").strip()
    tmp = os.path.join(out_dir, "tmp")
    os.makedirs(tmp, exist_ok=True)
    env["TMPDIR"] = tmp        # the profile lands here, inside the checkout
    report = os.path.join(out_dir, "device.json")
    flags = list(cell["config"]["server_flags"])
    if trace:
        flags += ["--flight-recorder", "--trace", "--http-port", "0",
                  "--debug-trace"]
    cmd = [sys.executable, "-m", "chipbench.serve_child", report,
           "--port", "0"] + flags
    log_path = os.path.join(out_dir, "server.stderr")
    with open(log_path, "wb") as err:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                stdout=subprocess.PIPE, stderr=err)
    clean = False
    try:
        yield Server(proc, _read_banner(proc), log_path, report)
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        if proc.wait(timeout=90) != 0:
            raise RunFailure(f"the server exited with code "
                             f"{proc.returncode} on SIGTERM")
        clean = True
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        proc.stdout.close()
        if not clean:
            with open(log_path, errors="replace") as fh:
                sys.stderr.write(f"--- stderr of {' '.join(cmd)}\n"
                                 f"{fh.read()[-8000:]}\n--- end stderr\n")


# --------------------------------------------------------------- the trace

def fetch_profile(http_port: int, box: dict, clock=time.monotonic) -> None:
    """Ask for the capture and wait for its body for as long as the
    gateway's heartbeat keeps arriving, up to CAPTURE_CEILING_S. Leaves
    ``profile`` or ``error`` in ``box``, and ``waited_s`` either way."""
    t0 = clock()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/debug/profile"
                f"?seconds={PROFILE_S:g}", timeout=HEARTBEAT_GAP_S) as resp:
            body = bytearray()
            while True:
                chunk = resp.read1(65536)     # returns with what has come
                if not chunk:
                    break
                body += chunk
                if clock() - t0 > CAPTURE_CEILING_S:
                    raise TimeoutError(
                        f"still no body after {clock() - t0:.0f} s of "
                        f"heartbeats (ceiling {CAPTURE_CEILING_S:g} s)")
        box["profile"] = json.loads(body)
    except Exception as exc:  # noqa: BLE001 — reported by the caller
        box["error"] = repr(exc)
    box["waited_s"] = clock() - t0


def capture_of(box: dict, thread) -> dict:
    """What /debug/profile answered, once ``thread`` (fetch_profile) has
    ended; RunFailure where there is no capture to reduce. A traced run
    never goes on to a result line without its device metrics."""
    patience = CAPTURE_CEILING_S + 2 * HEARTBEAT_GAP_S   # fetch_profile's own
    thread.join(timeout=patience)
    if thread.is_alive():
        raise RunFailure(f"/debug/profile (stop_trace) has not answered "
                         f"{patience:g} s after the capture was asked for")
    waited = f"waited {box.get('waited_s', float('nan')):.0f} s"
    if "error" in box:
        raise RunFailure(f"/debug/profile (stop_trace) failed, {waited}: "
                         f"{box['error']}")
    profile = box.get("profile")
    if not isinstance(profile, dict) or not profile.get("ok"):
        raise RunFailure(f"/debug/profile (stop_trace) gave no capture, "
                         f"{waited}: {profile!r}")
    return profile


def reduce_trace(profile: dict, out_dir: str):
    """Run chipbench/trace_reduce.py in a child pinned to the CPU, keep
    the reduced JSON and delete the raw trace."""
    pbs = [os.path.join(profile["dir"], f) for f in profile["files"]
           if f.endswith(".xplane.pb")]
    if not pbs:
        return None
    dst = os.path.join(out_dir, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.trace_reduce", pbs[0], dst],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    size = os.path.getsize(pbs[0])
    shutil.rmtree(profile["dir"], ignore_errors=True)
    if done.returncode != 0:
        raise RunFailure(f"trace_reduce failed:\n{done.stderr[-4000:]}")
    with open(dst) as fh:
        reduced = json.load(fh)
    say("trace", xplane_bytes=size, window_s=reduced["window_s"],
        step=reduced["step"], modules=reduced["modules"][:6],
        per_chip=[{k: d[k] for k in ("plane", "busy_s", "idle_pct", "gaps",
                                     "longest_gap_s")}
                  for d in reduced["devices"]])
    return reduced


# ------------------------------------------------------------ the run

def check(ok: bool, what: str, failures: list) -> None:
    if not ok:
        failures.append(what)


def admitted_cap(cfg: dict, run_s: float) -> int:
    """Most one key may be allowed in ``run_s`` seconds under the rule."""
    limit, window = cfg["limit"], cfg["window_s"]
    if cfg["algorithm"] == "token_bucket":
        return limit + math.ceil(run_s * limit / window)
    return limit * (int(run_s // window) + 1)


def holes(per_second: list) -> dict:
    """The seconds of the window whose completions are under half the
    run's median second: a run that stood still for a stretch reads as a
    slower program (PERF.md section 6, PR 31) and this line says so. It
    changes no result."""
    done = [s["completed"] for s in per_second]
    if not done:
        return {"median_per_s": None, "seconds": [], "held_s": 0}
    median = statistics.median(done)
    slow = [i for i, n in enumerate(done) if n < median / 2]
    return {"median_per_s": median, "seconds": slow, "held_s": len(slow)}


def held_numbers(cfg: dict, probed: dict, gen: dict, cold: dict,
                 policy: float, errors: float, served: int) -> list:
    """Each number `correct` rests on: (name, value, its limit, what it
    means when it is over). The probe stops at its first reply that
    differs from the reference, so it counts 0 or 1; ``policy`` and
    ``errors`` are the server's own counts (promtext), held against what
    the generator saw."""
    run_s = gen["run_s"] + DRAIN_S
    cap = admitted_cap(cfg, run_s)
    worst = max(gen["top_allowed"], default=0)
    client_done = probed["sent"] + gen["all"]["completed"] + cold["sent"]
    return [
        ("probe_replies_differing", 0 if probed["sent"] else 1, 0, None),
        ("hot_key_allowed_max", worst, cap,
         f"a hot key was allowed {worst} times in {run_s:g} s; the rule "
         f"admits at most {cap}"),
        ("cold_false_deny_pct", cold["cold_false_deny_pct"], 1.0,
         f"{cold['denied']} of {cold['sent']} never-seen keys denied"),
        ("cold_policy_answers", cold["policy"], 0,
         f"{cold['policy']} never-seen keys answered by policy"),
        ("policy_answers_unseen", max(0, policy - gen["all"]["policy"]), 0,
         f"server metrics: {policy:g} decisions answered by policy, the "
         f"generator saw {gen['all']['policy']}"),
        ("dispatch_errors_unseen",
         max(0, errors - gen["all"]["error_frames"]), 0,
         f"server metrics: {errors:g} dispatch errors, the generator saw "
         f"{gen['all']['error_frames']}"),
        ("decisions_server_short", max(0, client_done - served), 0,
         f"the server counted {served} decisions, the client completed "
         f"{client_done}"),
    ]


def result_line(correct: bool, gen: dict, metrics: dict, device: dict,
                breakdown=None, compared=None) -> dict:
    """The one object the driver reads. ``compared`` (name -> (number,
    its limit)) comes last."""
    failed = (gen["policy"] + gen["error_decisions"]
              + (gen["unanswered"] if gen["loop"] == "open" else 0))
    line = {"correct": bool(correct), "attempted": gen["sent"],
            "failed": failed,
            "metrics": {name: {"value": value, "unit": unit}
                        for name, (value, unit) in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["compared"] = {name: {"value": value, "limit": limit}
                            for name, (value, limit) in compared.items()}
    return line


def end_to_end(cell: dict, gen: dict, setup_s: float) -> dict:
    """name -> (value, unit) for the cell's end-to-end metrics."""
    have = {"setup_s": setup_s,
            "decisions_per_s": gen["completed"] / gen["window_s"],
            "latency_p50_ms": gen["latency_ms"]["p50"],
            "latency_p99_ms": gen["latency_ms"]["p99_median_of_seconds"]}
    return {m["name"]: (have[m["name"]], m["unit"])
            for m in cell_metrics(cell, "end_to_end")}


def per_layer(cell: dict, sources: dict) -> dict:
    """Run every reader that applies; name -> (value, unit) for those the
    manifest lists for this cell. The rest go on an earlier line."""
    listed = {m["name"] for m in cell_metrics(cell, "per_layer")}
    facts = {k: cell[k] for k in ("name", "chips", "config", "traffic")}
    out, extra = {}, {}
    for mod in layers.load():
        if not mod.META["applies"](facts):
            continue
        value = mod.read(sources)
        if value is None:
            continue
        if mod.META["name"] in listed:
            out[mod.META["name"]] = (value, mod.META["unit"])
        else:
            extra[mod.META["name"]] = value
    if extra:
        say("unlisted_layer_metrics", **extra)
    return out


def drive(gen: Generator, srv: Server, trace: bool) -> tuple:
    """Warm-up and window: the generator (spawned before the server, its
    tables built beside the server's start) is given the port and its
    start instant, and the window is where ITS schedule line puts it
    (CLOCK_MONOTONIC, both sides'). Traced, the window also holds the
    /metrics scrapes at its two ends and the profile. Returns the
    generator's JSON, the scrapes and what /debug/profile answered (None
    where the server has no gateway: never in a traced run of ``run``).
    ``run`` stops the generator on every way out."""
    schedule = gen.start(srv.port)
    t_win0, t_win1 = schedule["t_window_start"], schedule["t_window_end"]
    box: dict = {}
    scrapes: dict = {}
    prof_thread = None
    if trace:
        time.sleep(max(0.0, t_win0 - time.monotonic()))
        with Wire(srv.port) as wire:
            scrapes["start"] = (time.monotonic(), wire.metrics())
        if srv.http_port:
            prof_thread = threading.Thread(
                target=fetch_profile, args=(srv.http_port, box),
                daemon=True)
            time.sleep(max(0.0, t_win0 + 1.0 - time.monotonic()))
            prof_thread.start()
        time.sleep(max(0.0, t_win1 - 0.05 - time.monotonic()))
        with Wire(srv.port) as wire:
            scrapes["end"] = (time.monotonic(), wire.metrics())
    out = gen.result()
    profile = capture_of(box, prof_thread) if prof_thread is not None \
        else None
    return out, scrapes, profile


def run(args) -> int:
    cell = load_cell(args.workload)
    trace = bool(args.trace)
    out_dir = os.path.join(HERE, "out",
                           f"{args.workload}-{args.seed}-{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    seconds = max(args.seconds, TRACED_MIN_S) if trace and not REHEARSAL \
        else args.seconds
    if not os.path.isdir(os.path.join(ROOT, "ratelimiter_tpu")):
        raise NoChip("no ratelimiter_tpu/ beside chipbench/: not a checkout "
                     "of the program")
    binary, build_s = build_loadgen()
    # The generator builds its tables beside the server's start: 1.6 s at
    # 20 M keys, ~9 s at 10^8, under the 25 s and more a server takes
    # (the probe's 0.14 s would hide none of it).
    generator = Generator(binary, cell, args.seed, seconds)
    try:
        return measure(args, cell, generator, out_dir, seconds, build_s)
    finally:
        generator.stop()


def measure(args, cell: dict, generator: Generator, out_dir: str,
            seconds: float, build_s: float) -> int:
    """The run from the server's start to the result line."""
    cfg, traffic, trace = cell["config"], cell["traffic"], bool(args.trace)
    failures: list = []
    t_server = time.monotonic()
    with serving(cell, out_dir, trace) as srv:
        start_s = time.monotonic() - t_server
        dev = srv.device
        say("server", banner=srv.banner, start_s=start_s,
            host_cores=os.cpu_count())
        if dev["platform"] != "tpu" and not (REHEARSAL
                                             and dev["platform"] == "cpu"):
            raise NoChip(f"the server's device is {dev['platform']}/"
                         f"{dev['kind']}, not a TPU (set JAX_PLATFORMS=cpu "
                         f"for a rehearsal)")
        if dev["count"] < cell["chips"]:
            raise NoChip(f"the cell asks for {cell['chips']} chips, the "
                         f"server sees {dev['count']}")
        if cell["chips"] > 1:
            check(sorted(srv.slices) == sorted(str(i) for i in
                                               range(dev["count"])),
                  f"mesh over x{dev['count']} devices, slice state on "
                  f"{srv.slices}", failures)
        peaks = None
        if not REHEARSAL:
            with open(os.path.join(HERE, "peaks.json")) as fh:
                table = json.load(fh)
            if dev["kind"] not in table:
                raise RunFailure(f"device kind {dev['kind']!r} is not in "
                                 f"chipbench/peaks.json")
            peaks = table[dev["kind"]]

        # (4) the probe, on the fresh server, on the cell's own lane.
        t_probe = time.monotonic()
        with Wire(srv.port) as wire:
            try:
                probed = probe.probe(wire, cfg, traffic["lane"], args.seed)
                say("probe", **probed)
            except probe.CheckFailed as exc:
                failures.append(str(exc))
                probed = {"sent": 0}
        t_probed = time.monotonic()
        probe_s = t_probed - t_probe

        # (5)+(6) warm-up and the window, on the generator's schedule.
        gen, scrapes, profile = drive(generator, srv, trace)
        with open(os.path.join(out_dir, "loadgen.json"), "w") as fh:
            json.dump(gen, fh)
        if trace:
            if profile is None:
                raise RunFailure("a traced run needs /debug/profile and the "
                                 "server's banner names no http port")
            # The two scrapes the readers see, as they came (the
            # `scrape_s` apart that the `scrapes` line gives).
            for end in ("start", "end"):
                with open(os.path.join(out_dir, f"metrics_window_{end}.txt"),
                          "w") as fh:
                    fh.write(scrapes[end][1])
        setup_s = gen["t_window_start"] - T_SPAWN

        # (7) after the window.
        with Wire(srv.port) as wire:
            cold = probe.cold_keys(wire, traffic["lane"])
            text = wire.metrics()
            served = wire.decisions_total()
        with open(os.path.join(out_dir, "metrics_end.txt"), "w") as fh:
            fh.write(text)
        samples = promtext.parse(text)
        server_log = srv.log()
    # The server has exited; its device report is written.
    with open(srv.report_path) as fh:
        report = json.load(fh)
    peak = max((d["peak_bytes_in_use"] or 0 for d in report["devices"]),
               default=0)

    policy, errors = promtext.policy_answered(samples), \
        promtext.dispatch_errors(samples)
    client_done = probed["sent"] + gen["all"]["completed"] + cold["sent"]
    held = held_numbers(cfg, probed, gen, cold, policy, errors, served)
    for _, value, limit, what in held[1:]:       # the probe said its own
        check(value <= limit, what, failures)
    compared = {name: (value, limit) for name, value, limit, _ in held}
    worst, cap = compared["hot_key_allowed_max"]
    check(gen["completed"] > 0, "no decision completed in the window",
          failures)
    check(not gen["io_failed"], "a generator connection broke", failures)

    say("loadgen", **{k: gen[k] for k in (
        "loop", "lane", "threads", "conns", "inflight", "frame_keys", "rate",
        "sent", "completed", "allowed", "policy", "error_frames",
        "unanswered", "backlog_max_frames", "slice_sent")},
        allowed_share=(gen["allowed"] / gen["completed"]
                       if gen["completed"] else None),
        top_allowed_max=worst, admitted_cap=cap,
        **({} if REHEARSAL else {"latency_ms": gen["latency_ms"],
                                 "gen_late_ms": gen["gen_late_ms"]}))
    say("per_second", slices=[
        {k: s[k] for k in (("completed", "frames", "pending_frames")
                           if REHEARSAL else s)} for s in gen["per_second"]])
    say("holes", **holes(gen["per_second"]))
    say("checks", cold=cold, server_decisions=served,
        client_decisions=client_done, policy_answered=policy,
        dispatch_errors=errors, failures=failures)
    prewarm = prewarm_s.read({"server_log": server_log}) or 0.0
    # gen_build_s rides under the server's start; what of it was still to
    # come when the probe was done is gen_wait_s, which setup_s holds.
    gen_wait_s = max(0.0, gen["t_ready"] - t_probed)
    say("setup", setup_s=setup_s, build_s=build_s,
        start_to_prewarm_s=start_s - prewarm, prewarm_s=prewarm,
        probe_s=probe_s, gen_build_s=gen["build_s"], gen_wait_s=gen_wait_s,
        gen_peak_rss_bytes=gen["peak_rss_bytes"], warmup_s=WARMUP_S,
        other_s=setup_s - build_s - start_s - probe_s - gen_wait_s
        - WARMUP_S)

    device = {**dev, "memory_peak_bytes": peak}
    correct = not failures
    breakdown = None
    if trace:
        reduced = reduce_trace(profile, out_dir)
        if not REHEARSAL and not (reduced and reduced["n_devices"]
                                  and reduced["busy_s"] > 0):
            raise RunFailure("the capture holds no operation on a device: "
                             "no device metric can be read from it")
        sources = {
            "cell": cell, "loadgen": gen, "trace": reduced, "peaks": peaks,
            "server_log": server_log,
            "metrics_start": promtext.parse(scrapes["start"][1]),
            "metrics_end": promtext.parse(scrapes["end"][1]),
            "scrape_s": scrapes["end"][0] - scrapes["start"][0]}
        say("scrapes", scrape_s=sources["scrape_s"])
        metrics = per_layer(cell, sources)
        if reduced:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = end_to_end(cell, gen, setup_s)
    shutil.rmtree(os.path.join(out_dir, "tmp"), ignore_errors=True)

    if "jax" in sys.modules:
        raise RunFailure("the runner imported jax: it would hold the chip")
    if REHEARSAL:
        say("rehearsal", workload=cell["name"], correct=correct,
            attempted=gen["sent"], device=dev,
            metric_names=sorted(metrics), failures=failures)
        print(f"rehearsal on {dev['platform']}: "
              f"{'passed' if correct else 'FAILED'}; no accelerator, so no "
              f"result")
        return 3 if correct else 1
    print(json.dumps(result_line(correct, gen, metrics, device, breakdown,
                                 compared)), flush=True)
    for what in failures:
        sys.stderr.write(f"chipbench: not correct: {what}\n")
    for name, (value, limit) in compared.items():
        sys.stderr.write(f"compared {name} = {value:g} (limit {limit:g})\n")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except NoChip as exc:
        sys.stderr.write(f"chipbench: {exc}\n")
        return 2
    except (RunFailure, probe.CheckFailed, OSError,
            subprocess.TimeoutExpired) as exc:
        sys.stderr.write(f"chipbench: {type(exc).__name__}: {exc}\n")
        return 1
