"""End-to-end serving benchmark: real server process, real wire protocol,
string keys — the number VERDICT r2 asked for ("including host ingest and
string hashing").

Topology: N pipelined AsyncClient connections drive a spawned
``python -m ratelimiter_tpu.serving`` subprocess; every request carries a
string key (hashed server-side by the native bulk hasher on the batched
path); the server coalesces across connections via the micro-batcher.

Three variants:
* exact backend — pure host path (no device), isolates RPC + batcher cost;
* sketch backend, default platform — the flagship path. Started from
  ``python -m benchmarks`` the parent already holds JAX, so on a chip
  host this child cannot have the chip (known; ROADMAP S1 replaces the
  runner — chip_smoke.py is the served path on the chip today);
* sketch backend, JAX_PLATFORMS=cpu — the host/RPC machinery with the
  decide step on the CPU device: a count and a correctness lane, never
  a device rate.
"""

from __future__ import annotations

import asyncio
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from ratelimiter_tpu.serving import AsyncClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_server(backend: str, *, platform: Optional[str] = None,
                  max_batch: int = 4096, max_delay_us: float = 500.0,
                  native: bool = False, shards: int = 1,
                  inflight: int = 8, mesh_devices: Optional[int] = None,
                  extra_env: Optional[Dict[str, str]] = None,
                  extra_args: Optional[list] = None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    if platform:
        env["JAX_PLATFORMS"] = platform
    if extra_env:
        env.update(extra_env)
    port = _free_port()
    algo = "sliding_window" if backend == "exact" else "tpu_sketch"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ratelimiter_tpu.serving",
         "--backend", backend, "--algorithm", algo,
         "--limit", "100", "--window", "60",
         "--max-batch", str(max_batch),
         "--max-delay-us", str(max_delay_us),
         "--inflight", str(inflight),
         "--port", str(port)]
        + (["--native"] if native else [])
        + (["--shards", str(shards)] if shards > 1 else [])
        + (["--mesh-devices", str(mesh_devices)]
           if mesh_devices is not None else [])
        + (list(extra_args) if extra_args else []),
        env=env, stdout=subprocess.PIPE, text=True)  # stderr: inherited
    line = proc.stdout.readline()  # blocks until "serving ..." banner
    if "serving" not in line:
        proc.kill()
        raise RuntimeError(f"server failed to start: {line!r}")
    return proc, port


async def _drive_scalar(port: int, *, seconds: float, conns: int,
                        inflight: int, n_keys: int, warmup: float = 2.0,
                        leased: bool = False,
                        lease_kw: Optional[Dict] = None) -> Dict:
    """The loadgen's ``leased`` mode (ADR-022) and its wire control.

    Closed-loop SCALAR ``allow()`` on a small zipf-hot keyset — the
    traffic shape leases exist for (per-key decisions, maximally
    repeated). ``leased=True`` enables the lease tier on every
    connection first, so decisions for hot keys are answered by the
    in-process cache under real concurrency (the maintenance loop
    renewing budgets while workers spend them); ``leased=False`` is
    the honest control: same client, same keys, every decision a
    pipelined wire RTT. The reported rate is CLIENT-OBSERVED either
    way — what an app embedding the client actually gets."""
    rng = np.random.default_rng(2)
    clients = [await AsyncClient.connect(port=port) for _ in range(conns)]
    caches = []
    if leased:
        from ratelimiter_tpu.observability import Registry

        kw = dict(hot_after=2, hot_window=60.0, low_water=0.5)
        kw.update(lease_kw or {})
        interval = kw.pop("interval", 0.02)
        for c in clients:
            # Own registry per cache: the local-answer counter is
            # registered by NAME, so DEFAULT-registry caches in one
            # process would all read the same (summed) series.
            caches.append(await c.enable_leases(
                interval=interval, registry=Registry(), **kw))
    t_measure = time.perf_counter() + warmup
    stop_at = t_measure + seconds
    counted = 0
    total = 0

    async def worker(c: AsyncClient, wid: int):
        nonlocal counted, total
        ids = rng.zipf(1.1, size=8192) % n_keys
        i = wid * 1291
        while time.perf_counter() < stop_at:
            for _ in range(256):
                await c.allow(f"hot:{ids[i % 8192]}")
                i += 1
            total += 256
            if time.perf_counter() >= t_measure:
                counted += 256
            # A fully-local burst never yields; give the lease
            # maintenance loop (and the other workers) the floor.
            await asyncio.sleep(0)

    await asyncio.gather(*(worker(c, w * conns + k)
                           for k, c in enumerate(clients)
                           for w in range(max(1, inflight))))
    end = time.perf_counter()
    local = sum(int(lc.status()["local_answers"]) for lc in caches)
    for c in clients:
        await c.close()
    span = max(end - t_measure, 1e-9)
    return {
        "mode": "leased" if leased else "wire",
        "decisions_per_sec": round(counted / span, 1),
        "completed": counted,
        "local_answers": local,
        "local_fraction": round(local / total, 4) if total else None,
        "connections": conns,
        "workers_per_conn": max(1, inflight),
        "hot_keys": n_keys,
    }


async def _drive(port: int, *, seconds: float, conns: int, window: int,
                 n_keys: int, warmup: float = 2.0,
                 trace_sample: int = 0) -> Dict:
    """Two passes over a live server:

    1. Throughput: each connection keeps `window` decisions in flight via
       pipelined ALLOW_BATCH frames (the Redis-pipelining analog); the
       first `warmup` seconds absorb jit compiles and are discarded.
    2. Latency: a single connection, ONE scalar request in flight — the
       uncontended per-request RTT (closed-loop saturated latency is just
       Little's law on the queue, so it is measured separately).

    ``trace_sample`` (ADR-014): every Nth frame per connection carries a
    fresh wire trace id and records a client-side "client" span — the
    loadgen half of the flight-recorder story (0 = off).
    """
    from ratelimiter_tpu.observability import tracing

    rng = np.random.default_rng(0)

    # ---- pass 1: saturated throughput via batch frames
    clients = [await AsyncClient.connect(port=port) for _ in range(conns)]
    frame = max(64, window // 4)  # keys per ALLOW_BATCH frame; 4 in flight
    t_measure = time.perf_counter() + warmup
    stop_at = t_measure + seconds
    counted = 0

    async def worker(c: AsyncClient):
        nonlocal counted
        ids = rng.zipf(1.1, size=65536) % n_keys
        i = 0

        async def one():
            nonlocal counted, i
            keys = [f"user:{ids[(i + j) % 65536]}" for j in range(frame)]
            tid = 0
            if trace_sample and (i // frame) % trace_sample == 0 \
                    and tracing.RECORDER is not None:
                tid = tracing.new_trace_id()
            i += frame
            if tid:
                t0 = tracing.now()
                await c.allow_batch(keys, trace_id=tid)
                tracing.record("client", t0, tracing.now(), trace_id=tid,
                               batch=frame)
            else:
                await c.allow_batch(keys)
            if time.perf_counter() >= t_measure:
                counted += frame

        pending = {asyncio.ensure_future(one())
                   for _ in range(max(1, window // frame))}
        while time.perf_counter() < stop_at:
            done, pending = await asyncio.wait(
                pending, return_when=asyncio.FIRST_COMPLETED)
            for d in done:
                d.result()
                if time.perf_counter() < stop_at:
                    pending.add(asyncio.ensure_future(one()))
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)

    await asyncio.gather(*(worker(c) for c in clients))
    end = time.perf_counter()
    for c in clients:
        await c.close()
    span = max(end - t_measure, 1e-9)

    # ---- pass 2: uncontended scalar latency
    c = await AsyncClient.connect(port=port)
    lats: List[float] = []
    for i in range(400):
        t0 = time.perf_counter()
        await c.allow(f"lat:{i % 100}")
        lats.append(time.perf_counter() - t0)
    await c.close()
    lat = np.array(lats[50:])  # drop connection/jit warmup tail

    return {
        "decisions_per_sec": round(counted / span, 1),
        "completed": counted,
        "scalar_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "scalar_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "connections": conns,
        "inflight_per_conn": window,
        "batch_frame": frame,
    }


def _run_variant(name: str, backend: str, *, platform=None, seconds=6.0,
                 conns=4, window=2048, native=False, trace_sample=0,
                 log=print) -> Dict:
    proc, port = _spawn_server(backend, platform=platform, native=native)
    try:
        out = asyncio.run(_drive(port, seconds=seconds, conns=conns,
                                 window=window, n_keys=100_000,
                                 trace_sample=trace_sample))
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
    out["variant"] = name
    out["backend"] = backend
    log(f"e2e {name}: {out['decisions_per_sec']:.0f}/s "
        f"scalar_p99={out['scalar_p99_ms']}ms")
    return out


def _run_native_loadgen(*, seconds: float, log=print,
                        inflight: int = 8, hashed: bool = False) -> Dict:
    """Native server driven by the native C++ load generator
    (clients/cpp/loadgen.cpp) — removes the Python client from the loop,
    so this is the true server+decide ceiling. ``inflight`` sets the
    server's pipelined dispatch window (1 = the old synchronous path);
    ``hashed`` drives the zero-copy ALLOW_HASHED lane (raw u64 ids,
    device-side hashing, ADR-011) instead of string ALLOW_BATCH frames."""
    import json
    import shutil
    import tempfile

    if shutil.which("g++") is None:
        return {"variant": "native server + native loadgen",
                "error": "no g++"}
    with tempfile.TemporaryDirectory() as td:
        binary = os.path.join(td, "rltpu_loadgen")
        subprocess.run(
            ["g++", "-O2", "-std=c++17",
             os.path.join(REPO, "clients", "cpp", "loadgen.cpp"),
             "-o", binary, "-pthread"],
            check=True, capture_output=True, timeout=180)
        # max_batch 16384: the CPU-device decide costs ~1 us/decision
        # flat, so deeper coalescing amortizes the per-dispatch overhead
        # (r4: C++-side key prefixing + responder-thread encode overlap
        # moved the ceiling from ~300K to ~0.8-1M/s on this harness; the
        # wall is the XLA-CPU step itself, see ADR-003). The pipelined
        # launch/resolve window (ADR-010) overlaps that step with host
        # encode/decode.
        proc, port = _spawn_server("sketch", platform="cpu", native=True,
                                   max_batch=16384, inflight=inflight)
        try:
            out = subprocess.run(
                [binary, "127.0.0.1", str(port), str(seconds), "6", "8",
                 "1024", "100000", "hashed" if hashed else "batch"],
                capture_output=True, text=True, timeout=seconds + 60)
            row = json.loads(out.stdout.strip())
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
    row["variant"] = ("NATIVE server + NATIVE loadgen, sketch on cpu "
                      "(no Python in the client loop; latency is per "
                      "1024-key frame, not per scalar request)")
    row["connections"] = row.pop("threads")
    row["inflight_per_conn"] = (row.pop("inflight_frames")
                                * row["keys_per_frame"])
    row["server_inflight"] = inflight
    log(f"e2e native+native (inflight={inflight}): "
        f"{row['decisions_per_sec']:.0f}/s")
    return row


def run_shm_ab(*, seconds: float = 4.0, pairs: int = 3,
               threads: int = 4, inflight: int = 8,
               frame_keys: int = 256, loadgen: Optional[str] = None,
               log=print) -> Dict:
    """Transport A/B for the zero-syscall shm wire lane (ADR-025):
    INTERLEAVED paired rounds of tcp-loopback / uds / shm through the
    C++ loadgen's hashed lane against real ``--native --shm`` servers —
    back-to-back rounds see the same box state, so the best paired
    ratio measures the transport's marginal cost, not machine drift
    (the same honesty pattern as the audit overhead A/B). Every row
    carries the loadgen's serialize/wire-write phase means, so the
    JSON shows WHERE the per-frame time went: encoding is
    transport-invariant, the write phase is the lane under test.

    Two servers, both shm-enabled: one TCP (serves the tcp and shm
    rounds — the shm lane upgrades over it) and one UDS (``--listen
    unix:...``). ``frame_keys`` is deliberately smaller than the
    saturation benches' 1024-2048: per-frame wire cost is the
    numerator here, and jumbo frames would hide it behind the device
    decide."""
    import json
    import shutil
    import tempfile

    if shutil.which("g++") is None:
        return {"error": "no g++"}
    td = None
    try:
        if loadgen is None:
            td = tempfile.mkdtemp()
            loadgen = _build_loadgen(td)
        upath = os.path.join(td or tempfile.gettempdir(),
                             f"rltpu-bench-{os.getpid()}.sock")
        tcp_proc, tcp_port = _spawn_server(
            "sketch", platform="cpu", native=True, max_batch=16384,
            inflight=inflight, extra_args=["--shm", "--limit", "1000000"])
        uds_proc = None
        try:
            uds_proc, _ = _spawn_server(
                "sketch", platform="cpu", native=True, max_batch=16384,
                inflight=inflight,
                extra_args=["--shm", "--limit", "1000000",
                            "--listen", f"unix:{upath}"])

            def run(transport: str) -> Dict:
                host = upath if transport == "uds" else "127.0.0.1"
                args = [loadgen, host, str(tcp_port), str(seconds),
                        str(threads), str(inflight), str(frame_keys),
                        "100000", "hashed", "--transport", transport]
                out = subprocess.run(args, capture_output=True, text=True,
                                     timeout=seconds + 90)
                return json.loads(out.stdout.strip())

            rounds = []
            for i in range(max(1, pairs)):
                rd = {t: run(t) for t in ("tcp", "uds", "shm")}
                rounds.append(rd)
                log(f"shm A/B round {i + 1}: "
                    + " ".join(f"{t}={rd[t]['decisions_per_sec']:.0f}/s"
                               f"(wr {rd[t]['wire_write_us_per_frame']:.2f}"
                               "us)" for t in ("tcp", "uds", "shm")))
        finally:
            for proc in (tcp_proc, uds_proc):
                if proc is None:
                    continue
                proc.terminate()
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
    finally:
        if td is not None:
            import shutil as _sh

            _sh.rmtree(td, ignore_errors=True)

    def best_pair(t: str) -> Dict:
        rd = max(rounds, key=lambda r: (r[t]["decisions_per_sec"]
                                        / max(r["tcp"]["decisions_per_sec"],
                                              1e-9)))
        return {
            "decisions_per_sec": rd[t]["decisions_per_sec"],
            "tcp_decisions_per_sec": rd["tcp"]["decisions_per_sec"],
            "vs_tcp": round(rd[t]["decisions_per_sec"]
                            / max(rd["tcp"]["decisions_per_sec"], 1e-9), 3),
            "frame_p50_ms": rd[t]["frame_p50_ms"],
            "frame_p99_ms": rd[t]["frame_p99_ms"],
        }

    wire = {t: round(min(r[t]["wire_write_us_per_frame"] for r in rounds),
                     3)
            for t in ("tcp", "uds", "shm")}
    return {
        "rounds": rounds,
        "paired_best": {"uds": best_pair("uds"), "shm": best_pair("shm")},
        "wire_write_us_per_frame_best": {
            **wire,
            "tcp_over_shm": round(wire["tcp"] / max(wire["shm"], 1e-9), 2),
        },
        "harness": (
            f"cpp_loadgen hashed lane, {threads} conns x {inflight} "
            f"pipelined {frame_keys}-id frames, interleaved "
            "tcp/uds/shm rounds against two --native --shm sketch-on-cpu "
            "servers (one tcp, one --listen unix:); paired_best is the "
            "round with the best transport/tcp ratio (drift cancels "
            "in-pair); wire_write_us is the loadgen's measured "
            "send-syscall (tcp/uds) or ring-push+doorbell (shm) phase "
            "per frame"),
    }


def _build_loadgen(td: str) -> str:
    binary = os.path.join(td, "rltpu_loadgen")
    subprocess.run(
        ["g++", "-O2", "-std=c++17",
         os.path.join(REPO, "clients", "cpp", "loadgen.cpp"),
         "-o", binary, "-pthread"],
        check=True, capture_output=True, timeout=180)
    return binary


def _net_counters(port: int):
    """(net_syscalls_total, decisions_total) scraped over the wire.

    Syscalls = the engine-maintained recv+writev+wait+wake counters
    (rate_limiter_net_syscalls_total, summed over ``kind``) — the
    numerator of the syscalls-per-decision figure NETENG_r01.json
    reports. The scrape itself rides the same socket path, so its own
    handful of syscalls lands in the delta; at bench volumes (1e5+
    decisions/round) that noise is < 1e-4 of the figure."""
    from ratelimiter_tpu.serving import Client

    c = Client("127.0.0.1", port)
    try:
        text = c.metrics()
        _, _, decisions = c.health()
    finally:
        c.close()
    sys_total = 0.0
    for line in text.splitlines():
        if line.startswith("rate_limiter_net_syscalls_total{"):
            sys_total += float(line.rsplit(" ", 1)[1])
    return sys_total, float(decisions)


def run_conn_sweep(*, seconds: float = 2.5, pairs: int = 2,
                   conns=(16, 64, 256, 512), frame_keys: int = 8,
                   inflight: int = 4, loadgen: Optional[str] = None,
                   log=print) -> Dict:
    """Connection-count sweep for the multi-ring network engine
    (ISSUE-20, ADR-026): INTERLEAVED paired rounds of baseline vs new
    engine at each connection count, C++ loadgen hashed lane, emitting
    per-row throughput, p99, and syscalls-per-decision into
    NETENG_r01.json.

    Two ``--native`` servers stay up for the whole sweep:

    * baseline — ``--net-engine epoll --io-rings 1`` plus
      ``RL_NET_COALESCE=0``, the bench-honesty env knob that restores
      the pre-ISSUE-20 write profile (one send syscall per reply frame,
      one eventfd ding per queued reply) in the SAME binary, so the
      pair measures the engine work and not build drift;
    * engine — ``--net-engine auto`` (best available backend, auto ring
      count), the shipped default.

    ``frame_keys`` is deliberately tiny (8): per-frame wire cost is the
    numerator under test, and jumbo frames would hide it behind the
    device decide (same honesty note as run_shm_ab). Rounds alternate
    baseline/engine back-to-back per connection count so machine drift
    cancels in-pair; syscalls-per-decision is computed from counter
    deltas around each round (engine-maintained counters, not strace)."""
    import json
    import shutil
    import tempfile

    if shutil.which("g++") is None:
        return {"error": "no g++"}
    td = None
    rows: List[Dict] = []
    try:
        if loadgen is None:
            td = tempfile.mkdtemp()
            loadgen = _build_loadgen(td)
        base_proc, base_port = _spawn_server(
            "sketch", platform="cpu", native=True, max_batch=16384,
            inflight=inflight,
            extra_args=["--net-engine", "epoll", "--io-rings", "1",
                        "--limit", "1000000"],
            extra_env={"RL_NET_COALESCE": "0"})
        eng_proc = None
        try:
            eng_proc, eng_port = _spawn_server(
                "sketch", platform="cpu", native=True, max_batch=16384,
                inflight=inflight,
                extra_args=["--net-engine", "auto",
                            "--limit", "1000000"])

            def run(port: int, n_conns: int) -> Dict:
                pre_sys, pre_dec = _net_counters(port)
                out = subprocess.run(
                    [loadgen, "127.0.0.1", str(port), str(seconds),
                     str(n_conns), str(inflight), str(frame_keys),
                     "100000", "hashed"],
                    capture_output=True, text=True, timeout=seconds + 120)
                row = json.loads(out.stdout.strip())
                post_sys, post_dec = _net_counters(port)
                d_dec = max(post_dec - pre_dec, 1.0)
                row["syscalls_per_decision"] = round(
                    (post_sys - pre_sys) / d_dec, 4)
                return row

            for n_conns in conns:
                for i in range(max(1, pairs)):
                    rd = {"conns": n_conns, "round": i,
                          "baseline": run(base_port, n_conns),
                          "engine": run(eng_port, n_conns)}
                    rows.append(rd)
                    log(f"conn-sweep {n_conns}c round {i + 1}: "
                        f"base={rd['baseline']['decisions_per_sec']:.0f}/s"
                        f"({rd['baseline']['syscalls_per_decision']:.3f} "
                        "sys/dec) "
                        f"engine={rd['engine']['decisions_per_sec']:.0f}/s"
                        f"({rd['engine']['syscalls_per_decision']:.3f} "
                        "sys/dec)")
            eng_net = _engine_probe(eng_port)
        finally:
            for proc in (base_proc, eng_proc):
                if proc is None:
                    continue
                proc.terminate()
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
    finally:
        if td is not None:
            import shutil as _sh

            _sh.rmtree(td, ignore_errors=True)

    def best_pair(n_conns: int) -> Dict:
        cand = [r for r in rows if r["conns"] == n_conns]
        rd = max(cand, key=lambda r: (r["engine"]["decisions_per_sec"]
                                      / max(r["baseline"]
                                            ["decisions_per_sec"], 1e-9)))
        b, e = rd["baseline"], rd["engine"]
        return {
            "baseline_decisions_per_sec": b["decisions_per_sec"],
            "engine_decisions_per_sec": e["decisions_per_sec"],
            "throughput_ratio": round(e["decisions_per_sec"]
                                      / max(b["decisions_per_sec"], 1e-9),
                                      3),
            "baseline_syscalls_per_decision": b["syscalls_per_decision"],
            "engine_syscalls_per_decision": e["syscalls_per_decision"],
            "syscall_cut": round(b["syscalls_per_decision"]
                                 / max(e["syscalls_per_decision"], 1e-9),
                                 2),
            "baseline_frame_p99_ms": b["frame_p99_ms"],
            "engine_frame_p99_ms": e["frame_p99_ms"],
        }

    return {
        "rows": rows,
        "paired_best": {str(n): best_pair(n) for n in conns},
        "engine": eng_net,
        "harness": (
            f"cpp_loadgen hashed lane, {frame_keys}-id frames x "
            f"{inflight} pipelined, interleaved baseline/engine rounds "
            "per connection count against two --native sketch-on-cpu "
            "servers (baseline: --net-engine epoll --io-rings 1 + "
            "RL_NET_COALESCE=0 = pre-ISSUE-20 write-per-frame profile; "
            "engine: --net-engine auto); syscalls_per_decision from "
            "engine counter deltas (rate_limiter_net_syscalls_total) "
            "around each round; paired_best is the round with the best "
            "engine/baseline throughput ratio (drift cancels in-pair)"),
    }


def _engine_probe(port: int) -> Dict:
    """The engine/rings/probe identity of a live server, via /metrics
    (rate_limiter_net_engine_info labels) — recorded in NETENG_r01.json
    so the row says WHICH backend produced it."""
    from ratelimiter_tpu.serving import Client

    c = Client("127.0.0.1", port)
    try:
        text = c.metrics()
    finally:
        c.close()
    for line in text.splitlines():
        if line.startswith("rate_limiter_net_engine_info{"):
            labels = line[line.index("{") + 1:line.index("}")]
            out = {}
            for part in labels.split(","):
                k, _, v = part.partition("=")
                out[k.strip()] = v.strip().strip('"')
            return out
    return {}


def run_mesh_loadgen(n_devices: int, *, seconds: float = 4.0,
                     affine: bool = True, spread: Optional[int] = None,
                     loadgen: Optional[str] = None,
                     platform: Optional[str] = None,
                     router: str = "host",
                     chaos: Optional[str] = None,
                     chaos_slice: int = 1,
                     chaos_after: float = 1.0) -> Dict:
    """One measured point of the slice-parallel serving curve (ADR-012):
    a real ``--backend mesh --native`` server over ``n_devices`` pinned
    slices, driven by the C++ loadgen's zero-copy hashed lane.

    ``spread`` is the slice-spread knob (ADR-013): each connection's ids
    route to a window of that many dispatch shards starting at its home
    shard (splitmix64(id) % n). spread=1 is pure shard-affine traffic —
    the shape a consistent-hash LB produces, frames never fan out;
    spread=n is uniform MIXED traffic — every frame fans out over every
    device and reassembles through the scatter-gather scheduler. When
    ``spread`` is None, ``affine`` selects spread=1 (True) or spread=n
    (False). The server always routes every id itself either way.

    ``--inflight 1`` (synchronous per-shard dispatch): on the CPU mesh
    the jitted step executes synchronously inside launch, so pipelining
    only fragments coalesced batches across window slots; each device's
    dispatcher thread blocking in its own decide IS the parallelism
    (the GIL is released while the device computes).

    ``router="collective"`` (ADR-024) serves the same traffic through
    the collective mesh router: the composite limiter mounts as ONE
    dispatch shard and every frame is one shard_map'd all_to_all step —
    the id generation (and therefore the affine/mixed traffic shape,
    which both routers define by the same ``h64 % n`` owner rule) is
    unchanged, so host and collective rows are directly comparable."""
    import json
    import shutil
    import tempfile

    if shutil.which("g++") is None:
        return {"error": "no g++"}
    if chaos and router == "collective":
        # The slice chaos scenarios need --quarantine, which the
        # collective router refuses (whole-mesh blast radius, ADR-024).
        raise ValueError("chaos scenarios need the host router "
                         "(--quarantine is incompatible with "
                         "router='collective')")
    if spread is None:
        spread = 1 if affine else n_devices
    spread = max(1, min(int(spread), n_devices))
    with tempfile.TemporaryDirectory() as td:
        binary = loadgen or _build_loadgen(td)
        # Chaos-enabled runs (ADR-015): the server arms one scenario
        # mid-traffic and quarantine contains it; the loadgen keeps
        # driving through the fault — fail-open answers count as served
        # (the row reports the degraded-but-serving rate).
        chaos_args = []
        if chaos:
            chaos_args = ["--fail-open", "--quarantine",
                          "--chaos-scenario", chaos,
                          "--chaos-slice", str(chaos_slice),
                          "--chaos-after", str(chaos_after)]
        if router != "host":
            chaos_args = chaos_args + ["--router", router]
        proc, port = _spawn_server(
            "mesh", platform=platform, native=True, max_batch=16384,
            max_delay_us=1000.0, inflight=1, mesh_devices=n_devices,
            extra_args=chaos_args)
        try:
            # 16 conns x 8 x 2048 ids = 262K in flight: enough offered
            # load to keep EIGHT devices' coalescers at max_batch depth
            # (thin queues half-fill the per-device batches and flatten
            # the top of the scaling curve).
            args = [binary, "127.0.0.1", str(port), str(seconds), "16", "8",
                    "2048", "1000000", "hashed", str(n_devices),
                    str(spread)]
            out = subprocess.run(args, capture_output=True, text=True,
                                 timeout=seconds + 120)
            row = json.loads(out.stdout.strip())
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
    row["n_devices"] = n_devices
    row["router"] = router
    if chaos:
        row["chaos"] = {"scenario": chaos, "victim_slice": chaos_slice,
                        "armed_after_s": chaos_after}
    row["traffic"] = (
        "shard-affine (consistent-hash LB shape)" if spread == 1
        else ("mixed (uniform per-frame fan-out, scatter-gather "
              "coalesced)" if spread >= n_devices
              else f"partially mixed (slice-spread {spread}/{n_devices})"))
    return row


def run_e2e(quick: bool = False, trace_sample: int = 0,
            log=print) -> List[Dict]:
    """``trace_sample=N`` (ADR-014) turns on the loadgen's client-side
    flight recorder and samples every Nth frame per connection with a
    wire trace id: client spans land in the local recorder, and a
    server started with ``--flight-recorder`` attributes its stages to
    the same ids (``python -m benchmarks --only e2e --trace-sample N``)."""
    from ratelimiter_tpu.observability import tracing

    if trace_sample:
        tracing.enable()
    seconds = 2.0 if quick else 6.0
    window = 512 if quick else 2048
    rows = []
    rows.append(_run_variant("host-only (exact backend)", "exact",
                             seconds=seconds, window=window,
                             trace_sample=trace_sample, log=log))
    rows.append(_run_variant("sketch on cpu device", "sketch",
                             platform="cpu", seconds=seconds, window=window,
                             trace_sample=trace_sample, log=log))
    try:
        rows.append(_run_variant(
            "NATIVE server, host-only (exact backend)", "exact",
            seconds=seconds, window=window, native=True,
            trace_sample=trace_sample, log=log))
        rows.append(_run_variant(
            "NATIVE server, sketch on cpu device", "sketch",
            platform="cpu", seconds=seconds, window=window, native=True,
            trace_sample=trace_sample, log=log))
        rows.append(_run_native_loadgen(seconds=seconds, log=log))
    except Exception as exc:  # no compiler -> skip, never fail the suite
        rows.append({"variant": "native server", "error": str(exc)})
    if not quick:
        try:
            rows.append(_run_variant(
                "sketch on default platform",
                "sketch", seconds=seconds, window=window, log=log))
        except Exception as exc:  # a child that cannot get the device
            rows.append({"variant": "sketch on default platform",
                         "error": str(exc)})
    if trace_sample and tracing.RECORDER is not None:
        # Surface the sampled client spans so the run proves its own
        # sampling: count + RTT stats across every variant's loadgen.
        summary = tracing.RECORDER.stage_summary().get("client")
        rows.append({"variant": f"loadgen trace sampling (1/{trace_sample} "
                                "frames)",
                     "client_spans": summary or {"count": 0}})
        tracing.disable()
    return rows
