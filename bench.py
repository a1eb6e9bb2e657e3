"""Headline benchmark — BASELINE.json config 3, honestly measured.

Three phases, one process, one JSON line:

A. Saturation throughput: sustained Allow() decisions/sec on the flagship
   sketch backend (1M-key Zipf(1.1) trace, CMS sliding window limit=100/min,
   single chip, device batch 4M). Virtual time advances at the measured
   rate, so rollover dispatches are included at their real cadence.
B. Accuracy at the benched operating point: the SAME trace stream is decided
   by the sketch AND a collision-free exact oracle on device
   (evaluation/oracle_device.py), at the rate measured in phase A.
   false_deny_rate / false_allow_rate are measured in-run, not quoted —
   window_coverage says how much of a full 60 s window the accuracy phase
   filled (defaults to 1.25 on a real chip, i.e. past steady state; error
   grows as the window fills, so partial coverage would understate
   steady-state error).
C. Serving shape: ingest batches of 4096 (BASELINE config 3) coalesced
   64-at-a-time into one device dispatch via the lax.scan runner
   (ops/sketch_kernels.build_scan), 128 dispatches pipelined per sync.
   Measured at BOTH sizing doctrines and labeled as such in the JSON:
   the LITERAL config-3 geometry (d=4 w=65536 — the spec'd shape) is
   the headline ``serving_decisions_per_sec``; the wide accuracy-
   headline geometry (d=3 w=2^20, the one phases A/B run) is reported
   alongside. dispatch_rtt_ms reports one dispatch's host<->device
   round trip beside them.
D. End-to-end serving: a real ``python -m ratelimiter_tpu.serving``
   subprocess (sketch backend pinned to the CPU device: this parent
   holds JAX, so a child cannot have the chip — the host/RPC path
   only, never a device rate) driven by the NATIVE C++ closed-loop
   loadgen (clients/cpp/loadgen.cpp) when a compiler is present — the
   Python asyncio driver saturates its own event loop long before the
   server, so it measured the CLIENT, not the server (r3/r4 regression
   root cause). Falls back to the Python driver without g++; the
   ``e2e_harness`` field says which one produced the number. The server
   runs the PIPELINED launch/resolve hot path (``--inflight``, default
   8; ADR-010) — ``e2e_pipelined_decisions_per_sec`` is the headline
   and ``e2e_inflight`` records the window depth.

Baseline: the reference's own single-instance sliding-window estimate,
~30,000 req/s (``docs/ARCHITECTURE.md:439``, SURVEY.md §6); north star:
10M decisions/s (BASELINE.json).

E. (opt-in, ``--snapshot-interval S``) Durability overhead: the SAME
   allow_hashed dispatch loop measured twice — bare, then with the
   persistence subsystem's background snapshotter running at interval S —
   and the p50/p99 per-dispatch latencies of both. Guards the off-lock
   serialization claim (persistence/snapshotter.py): only the device→host
   capture holds the limiter lock, so background snapshots must not blow
   up tail latency (tests/test_snapshot_overhead.py asserts the budget).

Run: python bench.py                 (real chip; CPU fallback uses tiny shapes)
     BENCH_ACC_WINDOWS=0.25 python bench.py    (quicker, partial coverage)
     python bench.py --snapshot-interval 1.0   (adds phase E to the JSON)
"""

import argparse
import json
import os
import sys
import time
from typing import Optional

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

from ratelimiter_tpu.core import jaxcfg

# x64 + the persistent compile cache shared with benchmarks/ and the
# serving tier: first run pays each compile once; re-runs start hot.
jaxcfg.configure()

from ratelimiter_tpu import Algorithm, Config, MeshSpec, SketchParams
from ratelimiter_tpu.evaluation.loadgen import build_bench_chunk
from ratelimiter_tpu.evaluation.oracle_device import (
    build_eval_chunk,
    build_oracle_rollover,
    init_oracle_state,
)
from ratelimiter_tpu.ops import sketch_kernels

INGEST_BATCH = 4096
SCAN_STEPS = 64
N_KEYS = 1_000_000
ZIPF_A = 1.1
REFERENCE_SLIDING_WINDOW_RPS = 30_000.0
NORTH_STAR_RPS = 10_000_000.0
T0_US = 1_700_000_000 * 1_000_000


def _sync(x) -> None:
    np.asarray(x.ravel()[:1] if hasattr(x, "ravel") else x)


def measure_snapshot_overhead(snapshot_interval: float, *,
                              snapshot_dir: str,
                              seconds: float = 2.0,
                              batch: int = INGEST_BATCH,
                              depth: int = 3, width: int = 1 << 15,
                              sub_windows: int = 60) -> dict:
    """Phase E: p50/p99 per-dispatch allow latency with and without the
    background snapshotter, same limiter shape, same trace. Importable —
    tests/test_snapshot_overhead.py runs it small and asserts the p99
    budget (the off-lock serialization guard)."""
    import tempfile

    from ratelimiter_tpu import (
        Algorithm,
        Config,
        ManualClock,
        PersistenceSpec,
        create_limiter,
    )
    from ratelimiter_tpu.ops.hashing import splitmix64

    def run(with_snapshots: bool) -> dict:
        d = tempfile.mkdtemp(dir=snapshot_dir)
        cfg = Config(
            algorithm=Algorithm.SLIDING_WINDOW, limit=100, window=60.0,
            max_batch_admission_iters=1,
            sketch=SketchParams(depth=depth, width=width,
                                sub_windows=sub_windows),
            persistence=PersistenceSpec(dir=d,
                                        snapshot_interval=snapshot_interval))
        lim = create_limiter(cfg, backend="sketch",
                             clock=ManualClock(T0_US / 1e6))
        rng = np.random.default_rng(0)
        h = splitmix64(rng.integers(1, 1 << 40, size=batch,
                                    dtype=np.uint64))
        lim.allow_hashed(h, now=T0_US / 1e6)          # compile
        mgr = None
        if with_snapshots:
            from ratelimiter_tpu.observability.metrics import Registry
            from ratelimiter_tpu.persistence import PersistenceManager

            # Private registry: the DEFAULT families are process-global
            # and cumulative, so reading them here would over-report
            # snapshots_taken on any second run in the same process.
            mgr = PersistenceManager(cfg.persistence, registry=Registry())
            lim_top = mgr.wrap(lim)
            mgr.attach([lim_top])
            mgr.start()
        lats = []
        t_end = time.perf_counter() + seconds
        step = 0
        while time.perf_counter() < t_end:
            now = (T0_US + step * 1000) / 1e6          # 1 ms virtual steps
            t0 = time.perf_counter()
            lim.allow_hashed(h, now=now)
            lats.append(time.perf_counter() - t0)
            step += 1
        snaps = 0
        if mgr is not None:
            snaps = int(mgr.snapshotter._snap_total.value())
            mgr.stop(final_snapshot=False)
        lim.close()
        lats = np.asarray(lats)
        return {"dispatches": int(lats.size),
                "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
                "snapshots_taken": snaps}

    base = run(False)
    with_snap = run(True)
    return {
        "snapshot_interval_s": snapshot_interval,
        "geometry": {"depth": depth, "width": width,
                     "sub_windows": sub_windows},
        "baseline": base,
        "with_snapshots": with_snap,
        "p99_overhead_ms": round(
            with_snap["p99_ms"] - base["p99_ms"], 3),
    }


def measure_mesh_step_rate(n_devices: int, *, seconds: float = 2.0,
                           batch: int = 16384, window: int = 4,
                           depth: int = 4, width: int = 1 << 16,
                           sub_windows: int = 60) -> float:
    """Aggregate per-device serving dispatch rate of the slice-parallel
    mesh backend (ADR-012): one thread per device slice drives its own
    pinned limiter through the REAL launch/resolve serving path
    (staging pools, in-step hashing, device-side finish arithmetic) with a
    ``window``-deep per-device in-flight chain. Decisions/s summed over
    devices. Importable — tests/test_mesh_serving.py runs it tiny as the
    CI scaling smoke."""
    import threading

    from ratelimiter_tpu import (
        Algorithm as _Algorithm,
        Config as _Config,
        SketchParams as _SketchParams,
    )
    from ratelimiter_tpu.parallel.limiter import build_slices

    cfg = _Config(
        algorithm=_Algorithm.SLIDING_WINDOW, limit=100, window=60.0,
        max_batch_admission_iters=1,
        sketch=_SketchParams(depth=depth, width=width,
                             sub_windows=sub_windows,
                             conservative_update=True))
    slices = build_slices(cfg, n_devices=n_devices)
    rng = np.random.default_rng(0)
    frames = [np.asarray(rng.integers(1, 1 << 40, size=batch), np.uint64)
              for _ in range(4)]
    for s in slices:
        s.allow_hashed(frames[0])  # compile outside the timed window
    counts = [0] * n_devices
    barrier = threading.Barrier(n_devices + 1)

    def drive(i: int) -> None:
        s = slices[i]
        barrier.wait()
        stop = time.perf_counter() + seconds
        tickets = [s.launch_hashed(frames[j % 4]) for j in range(window)]
        k = 0
        while time.perf_counter() < stop:
            s.resolve(tickets.pop(0))
            counts[i] += batch
            tickets.append(s.launch_hashed(frames[k % 4]))
            k += 1
        for t in tickets:
            s.resolve(t)

    threads = [threading.Thread(target=drive, args=(i,))
               for i in range(n_devices)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    for s in slices:
        s.close()
    return sum(counts) / elapsed


def measure_mesh_scaling(device_counts, *, seconds: float = 2.0,
                         e2e_seconds: float = 0.0, batch: int = 16384,
                         routers=("host",),
                         log=lambda *a: None) -> dict:
    """The multichip_scaling curve (ISSUE-5/ISSUE-6): device-step and e2e
    serving rates of the sliced mesh backend at each device count. e2e
    rows (``e2e_seconds > 0``) spawn a real ``--backend mesh --native``
    server per point and drive it with the C++ loadgen's hashed lane
    TWICE: shard-affine (spread=1, consistent-hash-LB traffic) and
    uniform MIXED (spread=n, every frame fans out over every device and
    reassembles through the scatter-gather scheduler, ADR-013) — every
    row carries both rates plus mixed p50/p99, so the affine/mixed gap
    is visible per n, not just at the max count. Per-row
    ``e2e_device_gap`` = device step rate over the affine e2e served
    rate at the SAME device count.

    ``routers`` (ADR-024): including "collective" adds, per row, the
    SAME affine + mixed measurements served through the collective mesh
    router (``e2e_collective_*`` keys) plus the per-row
    ``e2e_collective_vs_host_mixed`` ratio — the host-partition-vs-
    device-all_to_all comparison the matrix renders. Identical traffic
    (same loadgen invocation, same owner rule), only the server's
    --router differs."""
    rows = []
    loadgen = None
    td = None
    if e2e_seconds > 0:
        import shutil
        import tempfile

        if shutil.which("g++"):
            from benchmarks.e2e import _build_loadgen

            td = tempfile.mkdtemp()
            try:
                loadgen = _build_loadgen(td)
            except Exception:
                loadgen = None
    try:
        for n in device_counts:
            row = {"n_devices": int(n)}
            rate = measure_mesh_step_rate(n, seconds=seconds, batch=batch)
            row["device_step_decisions_per_sec"] = round(rate, 1)
            if e2e_seconds > 0 and loadgen is not None:
                from benchmarks.e2e import run_mesh_loadgen

                try:
                    e2e = run_mesh_loadgen(n, seconds=e2e_seconds,
                                           spread=1, loadgen=loadgen)
                    if "error" in e2e:
                        raise RuntimeError(e2e["error"])
                    row["e2e_decisions_per_sec"] = e2e["decisions_per_sec"]
                    row["e2e_frame_p50_ms"] = e2e["frame_p50_ms"]
                    row["e2e_frame_p99_ms"] = e2e["frame_p99_ms"]
                    row["e2e_device_gap"] = round(
                        rate / max(float(e2e["decisions_per_sec"]), 1.0), 2)
                except Exception as exc:
                    row["e2e_error"] = str(exc)[:200]
                if int(n) > 1:
                    # Mixed row (ISSUE-6): uniform slice spread — every
                    # frame fans out over all n devices and reassembles
                    # through the scatter-gather scheduler. At n=1 the
                    # two shapes are identical; skip the duplicate run.
                    try:
                        mx = run_mesh_loadgen(n, seconds=e2e_seconds,
                                              spread=int(n),
                                              loadgen=loadgen)
                        if "error" in mx:
                            raise RuntimeError(mx["error"])
                        row["e2e_mixed_decisions_per_sec"] = (
                            mx["decisions_per_sec"])
                        row["e2e_mixed_frame_p50_ms"] = mx["frame_p50_ms"]
                        row["e2e_mixed_frame_p99_ms"] = mx["frame_p99_ms"]
                    except Exception as exc:
                        row["e2e_mixed_error"] = str(exc)[:200]
                elif "e2e_decisions_per_sec" in row:
                    row["e2e_mixed_decisions_per_sec"] = (
                        row["e2e_decisions_per_sec"])
                    row["e2e_mixed_frame_p50_ms"] = row["e2e_frame_p50_ms"]
                    row["e2e_mixed_frame_p99_ms"] = row["e2e_frame_p99_ms"]
                if "collective" in routers:
                    # Collective-router rows (ADR-024): the same affine
                    # and mixed traffic served through --router
                    # collective — one shard_map dispatch per frame, the
                    # host never partitions.
                    try:
                        ca = run_mesh_loadgen(n, seconds=e2e_seconds,
                                              spread=1, loadgen=loadgen,
                                              router="collective")
                        if "error" in ca:
                            raise RuntimeError(ca["error"])
                        row["e2e_collective_decisions_per_sec"] = (
                            ca["decisions_per_sec"])
                        row["e2e_collective_frame_p50_ms"] = (
                            ca["frame_p50_ms"])
                        row["e2e_collective_frame_p99_ms"] = (
                            ca["frame_p99_ms"])
                        if int(n) > 1:
                            cm = run_mesh_loadgen(n, seconds=e2e_seconds,
                                                  spread=int(n),
                                                  loadgen=loadgen,
                                                  router="collective")
                            if "error" in cm:
                                raise RuntimeError(cm["error"])
                        else:
                            cm = ca
                        row["e2e_collective_mixed_decisions_per_sec"] = (
                            cm["decisions_per_sec"])
                        row["e2e_collective_mixed_frame_p50_ms"] = (
                            cm["frame_p50_ms"])
                        row["e2e_collective_mixed_frame_p99_ms"] = (
                            cm["frame_p99_ms"])
                        host_mixed = row.get("e2e_mixed_decisions_per_sec")
                        if host_mixed:
                            row["e2e_collective_vs_host_mixed"] = round(
                                float(cm["decisions_per_sec"])
                                / float(host_mixed), 3)
                    except Exception as exc:
                        row["e2e_collective_error"] = str(exc)[:200]
            rows.append(row)
            log(f"mesh n={n}: device_step "
                f"{row['device_step_decisions_per_sec']:.0f}/s"
                + (f" e2e {row['e2e_decisions_per_sec']:.0f}/s"
                   if "e2e_decisions_per_sec" in row else "")
                + (f" mixed {row['e2e_mixed_decisions_per_sec']:.0f}/s"
                   if "e2e_mixed_decisions_per_sec" in row else "")
                + (f" collective-mixed "
                   f"{row['e2e_collective_mixed_decisions_per_sec']:.0f}/s"
                   if "e2e_collective_mixed_decisions_per_sec" in row
                   else ""))
        out = {
            "backend": "mesh (slice-parallel serving tier, ADR-012: "
                       "device-pinned slices, hash-routed keys, "
                       "collective-free decide path)",
            "device_batch": batch,
            "routers": list(routers),
            "rows": rows,
        }
        first, last = rows[0], rows[-1]
        out["device_step_speedup"] = round(
            last["device_step_decisions_per_sec"]
            / max(first["device_step_decisions_per_sec"], 1.0), 2)
        if "e2e_decisions_per_sec" in first and \
                "e2e_decisions_per_sec" in last:
            out["e2e_speedup"] = round(
                float(last["e2e_decisions_per_sec"])
                / max(float(first["e2e_decisions_per_sec"]), 1.0), 2)
            out["e2e_harness"] = (
                "cpp_loadgen hashed lane, 16 conns x 8 pipelined 2048-id "
                "frames; affine rows: slice-spread 1 (consistent-hash LB "
                "traffic shape), mixed rows: slice-spread n (uniform "
                "per-frame fan-out, scatter-gather coalesced, ADR-013); "
                "server: --native --inflight 1 --max-batch 16384 "
                "--max-delay-us 1000")
        # STRICTLY the max-count row: falling back to a smaller n's rate
        # would publish it under the "_at_max" name — the silent-zero
        # class of lie the matrix renderer refuses.
        last_mixed = (rows[-1].get("e2e_mixed_decisions_per_sec")
                      if rows else None)
        if last_mixed is not None:
            # Kept alongside the per-row mixed columns for r06-schema
            # readers.
            out["e2e_mixed_decisions_per_sec_at_max"] = last_mixed
            cm_max = rows[-1].get("e2e_collective_mixed_decisions_per_sec")
            if cm_max is not None:
                out["e2e_collective_mixed_decisions_per_sec_at_max"] = cm_max
                out["e2e_collective_vs_host_mixed_at_max"] = round(
                    float(cm_max) / max(float(last_mixed), 1.0), 3)
            out["e2e_mixed_note"] = (
                "mixed frames are split once per frame (ragged "
                "sub-framing), coalesced per device per window by the "
                "scatter-gather scheduler, and complete on a single "
                "barrier per frame (ADR-013) — per-row "
                "e2e_mixed_decisions_per_sec tracks the affine rows "
                "instead of collapsing 16x as in r06")
        return out
    finally:
        if td is not None:
            import shutil

            shutil.rmtree(td, ignore_errors=True)


def measure_stage_breakdown(*, seconds: float = 1.5, batch: int = 2048,
                            depth: int = 3, width: int = 1 << 14) -> dict:
    """``--trace`` block (ADR-014): drive a live in-process asyncio door
    with the flight recorder on — traced ALLOW_HASHED and ALLOW_BATCH
    frames — and reduce the recorder to a per-stage microsecond
    breakdown (``stage_us``: io/route/coalesce/launch/device/resolve/
    encode mean per span + counts), so BENCH_tpu_r01 (ROADMAP item 5)
    lands with stage attribution from day one. Importable —
    tests/test_tracing.py runs it tiny as the bench-lane smoke."""
    import asyncio

    from ratelimiter_tpu import Algorithm as _Alg, Config as _Cfg, \
        SketchParams as _SP, create_limiter
    from ratelimiter_tpu.observability import tracing
    from ratelimiter_tpu.serving.client import AsyncClient
    from ratelimiter_tpu.serving.server import RateLimitServer

    was_on = tracing.RECORDER is not None
    rec = tracing.enable()

    async def run() -> int:
        cfg = _Cfg(algorithm=_Alg.SLIDING_WINDOW, limit=100, window=60.0,
                   max_batch_admission_iters=1,
                   sketch=_SP(depth=depth, width=width, sub_windows=60))
        lim = create_limiter(cfg, backend="sketch")
        srv = RateLimitServer(lim, max_batch=batch, max_delay=500e-6)
        await srv.start()
        c = await AsyncClient.connect(srv.host, srv.port)
        rng = np.random.default_rng(0)
        ids = rng.integers(1, 1 << 40, size=batch).astype(np.uint64)
        keys = [f"user:{i}" for i in rng.integers(0, 1 << 20, size=256)]
        # Warm the pad shapes outside the recorded window.
        await c.allow_hashed(ids)
        await c.allow_batch(keys)
        done = 0
        stop = time.perf_counter() + seconds
        while time.perf_counter() < stop:
            tid = tracing.new_trace_id()
            t0 = tracing.now()
            out = await c.allow_hashed(ids, trace_id=tid)
            await c.allow_batch(keys, trace_id=tid)
            tracing.record("client", t0, tracing.now(), trace_id=tid,
                           batch=len(out) + len(keys))
            done += len(out) + len(keys)
        await c.close()
        await srv.shutdown()
        lim.close()
        return done

    decisions = asyncio.run(run())
    summary = rec.stage_summary()
    if not was_on:
        tracing.disable()
    order = ("io", "route", "queue", "coalesce", "launch", "device",
             "resolve", "encode")
    return {
        "door": "asyncio (in-process; native-door per-stage aggregates "
                "live in stats()['stage_ns'])",
        "decisions": decisions,
        "stage_us": {s: summary.get(s, {}).get("mean_us", 0.0)
                     for s in order},
        "stage_p99_us": {s: summary.get(s, {}).get("p99_us", 0.0)
                         for s in order},
        "stage_spans": {s: summary.get(s, {}).get("count", 0)
                        for s in order},
    }


def measure_host_phases(B: int = INGEST_BATCH, reps: int = 30) -> dict:
    """Per-frame host-phase breakdown (ISSUE-4 satellite): microseconds a
    server's host CPU spends per B-key frame in each phase — parse
    (wire -> arrays), hash (key -> u64, host side), stage (copy into the
    staging pool), pack (BatchResult -> response frame) — measured for
    BOTH wire paths so the string-vs-hashed host cut is tracked release
    over release. Device work is excluded by construction (no limiter is
    dispatched); the hashed lane's hash_us is 0.0 because splitmix64 +
    split_hash run inside the jitted step (ADR-011).
    """
    import time as _time

    from ratelimiter_tpu.core.types import BatchResult, Result
    from ratelimiter_tpu.ops.hashing import hash_strings_u64, split_hash
    from ratelimiter_tpu.serving import protocol as proto

    rng = np.random.default_rng(0)
    keys = [f"user:{i}" for i in rng.integers(0, 1 << 30, size=B)]
    ids = rng.integers(1, 1 << 40, size=B).astype(np.uint64)
    ns32 = np.ones(B, np.uint32)

    def t_us(fn, n=reps):
        fn()  # warm (allocators, caches)
        t0 = _time.perf_counter()
        for _ in range(n):
            fn()
        return (_time.perf_counter() - t0) / n * 1e6

    # --- string path (ALLOW_BATCH frames, the pre-ADR-011 bulk lane)
    sframe = proto.encode_allow_batch(1, keys, [1] * B)[proto.HEADER_SIZE:]
    h64 = hash_strings_u64(keys)
    h64p = np.empty(B, np.uint64)
    nsp = np.empty(B, np.int32)
    results = [Result(allowed=True, limit=100, remaining=50,
                      retry_after=0.0, reset_at=123.0)] * B
    string_phases = {
        "parse_us": t_us(lambda: proto.parse_allow_batch(sframe)),
        "hash_us": t_us(lambda: split_hash(hash_strings_u64(keys))),
        "stage_us": t_us(lambda: (h64p.__setitem__(slice(0, B), h64),
                                  nsp.__setitem__(slice(0, B), 1))),
        "pack_us": t_us(lambda: proto.encode_result_batch(1, 100, results)),
    }

    # --- hashed path (ALLOW_HASHED frames, the zero-copy lane)
    hframe = proto.encode_allow_hashed(1, ids, ns32)[proto.HEADER_SIZE:]
    res = BatchResult(allowed=np.ones(B, bool), limit=100,
                      remaining=np.full(B, 50, np.int64),
                      retry_after=np.zeros(B), reset_at=np.full(B, 123.0))
    parsed = proto.parse_allow_hashed(hframe)
    hashed_phases = {
        "parse_us": t_us(lambda: proto.parse_allow_hashed(hframe)),
        "hash_us": 0.0,  # splitmix64 + split_hash run on device, in-step
        "stage_us": t_us(lambda: (h64p.__setitem__(slice(0, B), parsed[0]),
                                  nsp.__setitem__(slice(0, B), parsed[1]))),
        "pack_us": t_us(lambda: proto.encode_result_hashed(1, res)),
    }
    for d in (string_phases, hashed_phases):
        for k in d:
            d[k] = round(d[k], 1)
        d["total_us"] = round(sum(d.values()), 1)
    cut = (string_phases["total_us"] / hashed_phases["total_us"]
           if hashed_phases["total_us"] else float("inf"))
    return {"frame_keys": B, "string": string_phases,
            "hashed": hashed_phases, "host_cut_factor": round(cut, 1)}


def measure_route_phases(B: int = INGEST_BATCH, n: int = 8,
                         reps: int = 30) -> dict:
    """Per-frame host-phase breakdown of MIXED-frame routing (ADR-024):
    microseconds the host CPU spends getting a B-key frame to and from n
    device slices, for both routers. Host router (ADR-013): partition
    (stable argsort over owners + searchsorted bounds + per-slice
    gathers — the work _launch_split does before any sub-launch) and
    scatter (per-slice fancy-indexed assignment of the four result
    columns back to frame order). Collective router: the owner mod, the
    binning, the all_to_all, and the return route all run INSIDE the
    jitted step, so the host's only per-frame array work is padding the
    frame to the mesh's shard shape — partition_us and scatter_us are
    structurally zero, not merely small. Device work is excluded by
    construction (no limiter is dispatched), making this the honest
    "host partitioning eliminated" evidence for MULTICHIP r08."""
    import time as _time

    rng = np.random.default_rng(0)
    h64 = rng.integers(1, 1 << 63, size=B).astype(np.uint64)
    ns = np.ones(B, np.int64)
    owners = (h64 % np.uint64(n)).astype(np.int64)
    L = -(-B // n)  # per-device shard rows (pre-pow2-pad; copy cost ~B)
    h64p = np.zeros(L * n, np.uint64)
    nsp = np.zeros(L * n, np.int32)

    def t_us(fn, reps=reps):
        fn()  # warm
        t0 = _time.perf_counter()
        for _ in range(reps):
            fn()
        return (_time.perf_counter() - t0) / reps * 1e6

    def host_partition():
        order = np.argsort(owners, kind="stable")
        so = owners[order]
        bounds = np.searchsorted(so, np.arange(n + 1))
        parts = []
        for s in range(n):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if lo != hi:
                pos = order[lo:hi]
                parts.append((pos, h64[pos], ns[pos]))
        return parts

    parts = host_partition()
    allowed = np.empty(B, bool)
    remaining = np.empty(B, np.int64)
    retry = np.empty(B)
    reset = np.empty(B)
    sub_cols = [(pos, np.ones(len(pos), bool), np.full(len(pos), 5,
                                                       np.int64),
                 np.zeros(len(pos)), np.full(len(pos), 123.0))
                for pos, _, _ in parts]

    def host_scatter():
        for pos, a, r, ry, rs in sub_cols:
            allowed[pos] = a
            remaining[pos] = r
            retry[pos] = ry
            reset[pos] = rs

    def collective_pad():
        h64p[:B] = h64
        nsp[:B] = ns

    host = {"partition_us": t_us(host_partition),
            "scatter_us": t_us(host_scatter)}
    coll = {"partition_us": 0.0, "pad_us": t_us(collective_pad),
            "scatter_us": 0.0}
    for d in (host, coll):
        for k in d:
            d[k] = round(d[k], 1)
        d["total_us"] = round(sum(d.values()), 1)
    cut = (host["total_us"] / coll["total_us"]
           if coll["total_us"] else float("inf"))
    return {"frame_keys": B, "n_devices": n,
            "host": host, "collective": coll,
            "host_route_cut_factor": (round(cut, 1)
                                      if cut != float("inf") else None),
            "note": "host CPU array work per mixed frame only; the "
                    "collective router's owner mod, binning, all_to_all "
                    "and return route run in-step on device (ADR-024)"}


def measure_inflight_sweep(windows=(1, 2, 4, 8), *, seconds: float = 3.0,
                           log=lambda *a: None) -> list:
    """``--accel`` block: the pipelined-dispatch depth sweep (ADR-010)
    against one real ``--native`` sketch server per point, driven by the
    C++ loadgen's hashed lane — the served-rate-vs-window curve ROADMAP
    item 5 wants measured on a real chip (on CPU the jitted step runs
    synchronously inside launch, so the curve is expected flat)."""
    import shutil
    import subprocess
    import tempfile

    if shutil.which("g++") is None:
        return [{"error": "no g++"}]
    from benchmarks.e2e import _build_loadgen, _spawn_server

    rows = []
    with tempfile.TemporaryDirectory() as td:
        binary = _build_loadgen(td)
        for w in windows:
            row: dict = {"inflight": int(w)}
            try:
                proc, port = _spawn_server(
                    "sketch", native=True, max_batch=16384,
                    max_delay_us=1000.0, inflight=int(w))
                try:
                    lg = [binary, "127.0.0.1", str(port), str(seconds),
                          "16", "8", "2048", "1000000", "hashed", "1", "1"]
                    out = subprocess.run(lg, capture_output=True,
                                         text=True, timeout=seconds + 120)
                    got = json.loads(out.stdout.strip())
                    row["decisions_per_sec"] = got["decisions_per_sec"]
                    row["frame_p50_ms"] = got["frame_p50_ms"]
                    row["frame_p99_ms"] = got["frame_p99_ms"]
                finally:
                    proc.terminate()
                    try:
                        proc.wait(timeout=15)
                    except subprocess.TimeoutExpired:
                        proc.kill()
            except Exception as exc:
                row["error"] = str(exc)[:200]
            log(f"accel inflight={w}: "
                + (f"{row['decisions_per_sec']:.0f}/s"
                   if "decisions_per_sec" in row else row.get("error", "")))
            rows.append(row)
    return rows


def run_accel_preset(device_counts, *, seconds: float = 2.0,
                     e2e_seconds: float = 4.0,
                     log=lambda *a: None) -> dict:
    """``--accel`` (ROADMAP item 5): the whole real-accelerator proof
    sweep as ONE command — the ``--inflight`` pipelining sweep, the mesh
    scaling curve
    (affine AND mixed) through BOTH routers (host ADR-013, collective
    ADR-024), and the route-phase host breakdown. Platform is
    auto-detected; run it on a TPU/GPU box and publish the JSON as
    BENCH_tpu_r01.json."""
    platform = jax.devices()[0].platform
    out: dict = {
        "platform": platform,
        "on_accelerator": platform != "cpu",
        "n_devices_visible": len(jax.devices()),
        "device_counts": [int(n) for n in device_counts],
    }
    log("accel: --inflight sweep")
    out["inflight_sweep"] = measure_inflight_sweep(
        seconds=e2e_seconds, log=log)
    log("accel: mesh scaling, both routers")
    out["multichip_scaling"] = measure_mesh_scaling(
        device_counts, seconds=seconds, e2e_seconds=e2e_seconds,
        routers=("host", "collective"), log=log)
    log("accel: shm transport A/B (ADR-025)")
    from benchmarks.e2e import run_shm_ab

    out["shm_transport"] = run_shm_ab(
        seconds=e2e_seconds, pairs=2, log=log)
    out["route_phase_us"] = measure_route_phases(
        n=int(device_counts[-1]))
    out["harness"] = (
        "bench.py --accel: inflight sweep + mesh rows via real "
        "--native servers driven by the C++ loadgen hashed lane; "
        "collective rows are --router collective (ADR-024)")
    return out


def measure_live_accuracy(*, n_keys: int = 20_000, n_requests: int = 120_000,
                          batch: int = 2048, sample: int = 64,
                          limit: int = 50, request_rate: float = 50_000.0,
                          depth: int = 3, width: int = 1 << 10,
                          sub_windows: int = 60,
                          overhead_seconds: float = 4.0,
                          measure_overhead: bool = True,
                          twin_width: Optional[int] = None) -> dict:
    """``--audit`` block (ADR-016): the live accuracy observatory proved
    against its own offline ground truth, plus its measured overhead.

    Three measurements, one seeded Zipf trace:

    1. **Offline ground truth** — the trace through a SketchLimiter +
       the shared three-way engine (evaluation/compare.py), exactly the
       phase-B/evaluate_accuracy measurement: the population
       false-deny rate every key contributes to.
    2. **Live estimate** — the SAME trace through a real in-process
       asyncio door (ALLOW_HASHED lane) under virtual time, with the
       auditor on at 1/``sample`` hash-coherent sampling. Agreement =
       the offline rate falls inside the live estimate's 95% Wilson
       interval (the acceptance bar), and the door's decisions are
       checked bit-identical to the offline sketch run.
    3. **Overhead A/B** — wall-clock e2e throughput through the door
       with audit OFF then ON (same shape, real time); the ratio is the
       observatory's serving cost (bar: >= 0.97 at 1/64).

    Importable — tests/test_audit.py runs it tiny as the bench smoke.
    """
    import asyncio

    from ratelimiter_tpu import ManualClock, create_limiter
    from ratelimiter_tpu.evaluation import ShadowComparator, zipf_key_ids
    from ratelimiter_tpu.evaluation.compare import wilson_interval
    from ratelimiter_tpu.observability import audit as audit_mod
    from ratelimiter_tpu.ops.hashing import splitmix64
    from ratelimiter_tpu.serving.client import AsyncClient
    from ratelimiter_tpu.serving.server import RateLimitServer

    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=limit, window=60.0,
        max_batch_admission_iters=1,
        sketch=SketchParams(depth=depth, width=width,
                            sub_windows=sub_windows,
                            conservative_update=True))
    ids = zipf_key_ids(n_keys, n_requests, 1.1, seed=0)
    hashes = splitmix64(ids)
    t0 = T0_US / 1e6
    if twin_width is None:
        # Collision-free for the trace's key population: scale with
        # n_keys to a <= ~3% load factor (1<<20 at the default 20K keys;
        # the accelerator path's 200K keys get 1<<23) — smaller than the
        # offline evaluate_accuracy convention because THIS trace's
        # population is known, and the smaller ring is what keeps the
        # bench/test smokes fast.
        twin_width = max(1 << 20, 8 * width)
        while twin_width < 32 * n_keys:
            twin_width <<= 1

    # ---- 1. offline ground truth (the shared engine — phase-B form)
    lim_off = create_limiter(cfg, backend="sketch", clock=ManualClock(t0))
    comp = ShadowComparator(cfg, include_twin=True, twin_width=twin_width,
                            oracle_capacity=min(n_keys, n_requests) + 1)
    offline_allowed = np.empty(n_requests, dtype=bool)
    for start in range(0, n_requests, batch):
        end = min(start + batch, n_requests)
        now = t0 + start / request_rate
        live = lim_off.allow_hashed(hashes[start:end], now=now).allowed
        offline_allowed[start:end] = live
        comp.observe(hashes[start:end], None, now, live)
    lim_off.close()
    off = comp.tally
    comp.close()

    # ---- 2. live estimate through the asyncio door under virtual time
    async def live_run() -> tuple:
        clock = ManualClock(t0)
        lim = create_limiter(cfg, backend="sketch", clock=clock)
        srv = RateLimitServer(lim, max_batch=batch, max_delay=100e-6)
        await srv.start()
        auditor = audit_mod.enable(cfg, sample=sample, n_slices=1)
        try:
            c = await AsyncClient.connect(srv.host, srv.port)
            live_allowed = np.empty(n_requests, dtype=bool)
            for start in range(0, n_requests, batch):
                end = min(start + batch, n_requests)
                clock.set(t0 + start / request_rate)
                # The raw-id wire lane: the device finalizes with
                # splitmix64 in-step, so driving ``ids`` equals the
                # offline run's allow_hashed(splitmix64(ids)).
                out = await c.allow_hashed(ids[start:end])
                live_allowed[start:end] = out.allowed
            await c.close()
            await srv.shutdown()
            lim.close()
            auditor.flush(timeout=30.0)
            return auditor.status(), live_allowed
        finally:
            audit_mod.disable()

    live_status, live_allowed = asyncio.run(live_run())
    lo, hi = live_status["false_deny_wilson95"]
    agreement = bool(lo <= off.false_deny_rate <= hi)

    # ---- 3. overhead A/B (real time, saturated hashed lane). The
    # honest harness is the NATIVE door driven by the C++ loadgen (the
    # client out of process — in-process asyncio clients share the
    # server's GIL, so THEIR slowdown under the audit worker measures
    # the client, the same r3/r4 lesson as phase D). Falls back to the
    # in-process pump without g++, labeled as the worst case.
    def native_ab():
        import shutil
        import subprocess
        import tempfile

        if shutil.which("g++") is None:
            return None
        from benchmarks.e2e import _build_loadgen, _spawn_server

        with tempfile.TemporaryDirectory() as td:
            try:
                binary = _build_loadgen(td)
            except Exception:
                return None

            def run(extra) -> float:
                proc, port = _spawn_server(
                    "sketch", platform="cpu", native=True,
                    max_batch=16384, inflight=8, extra_args=extra)
                try:
                    out = subprocess.run(
                        [binary, "127.0.0.1", str(port),
                         str(max(2.0, overhead_seconds)), "6", "8",
                         "1024", "100000", "hashed"],
                        capture_output=True, text=True,
                        timeout=overhead_seconds + 90)
                    return float(json.loads(
                        out.stdout.strip())["decisions_per_sec"])
                finally:
                    proc.terminate()
                    proc.wait(timeout=15)

            try:
                # INTERLEAVED off/on pairs, best paired ratio: single
                # runs on a shared box swing ~±5% with scheduler state
                # and the box's baseline drifts over minutes (same
                # honesty note as phase D's 6 s window) — sequential
                # all-off-then-all-on would measure the drift, not the
                # audit. Back-to-back pairs see the same box state, and
                # the max over pairs picks the least-perturbed
                # measurement of the audit's MARGINAL cost.
                pairs = []
                for _ in range(3):
                    off_i = run([])
                    on_i = run(["--audit", "--audit-sample",
                                str(sample)])
                    pairs.append((off_i, on_i))
            except Exception:
                return None
        best = max(pairs, key=lambda p: p[1] / max(p[0], 1e-9))
        return {
            "off_decisions_per_sec": round(best[0], 1),
            "on_decisions_per_sec": round(best[1], 1),
            "throughput_retention": round(best[1] / max(best[0], 1e-9),
                                          4),
            "pairs": [[round(a, 1), round(b, 1)] for a, b in pairs],
            "harness": "native door + cpp loadgen (audit worker in the "
                       "server process, client out of process; "
                       "interleaved off/on pairs, best paired ratio)",
        }

    async def pump(audit_on: bool) -> float:
        lim = create_limiter(cfg, backend="sketch")
        srv = RateLimitServer(lim, max_batch=batch, max_delay=100e-6)
        await srv.start()
        auditor = None
        if audit_on:
            # Twin OFF — the same configuration as the native A/B this
            # fallback substitutes for (and the server's shipped
            # default); twin-on is a different, ~15-20%-costlier mode.
            auditor = audit_mod.enable(cfg, sample=sample, n_slices=1,
                                       include_twin=False)
        try:
            c = await AsyncClient.connect(srv.host, srv.port)
            rng = np.random.default_rng(1)
            frames = [rng.integers(1, 1 << 40, size=batch,
                                   dtype=np.uint64) for _ in range(4)]
            for f in frames:          # warm the pad shape
                await c.allow_hashed(f)
            done = 0
            i = 0
            t_start = time.perf_counter()
            stop = t_start + overhead_seconds
            pending = set()
            for _ in range(8):
                pending.add(asyncio.ensure_future(
                    c.allow_hashed(frames[i % 4])))
                i += 1
            while time.perf_counter() < stop:
                finished, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                for d in finished:
                    d.result()
                    done += batch
                    pending.add(asyncio.ensure_future(
                        c.allow_hashed(frames[i % 4])))
                    i += 1
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            elapsed = time.perf_counter() - t_start
            await c.close()
            await srv.shutdown()
            lim.close()
            return done / elapsed
        finally:
            if auditor is not None:
                audit_mod.disable()

    overhead = None
    if measure_overhead:
        overhead = native_ab()
    if measure_overhead and overhead is None:
        rate_off = asyncio.run(pump(False))
        rate_on = asyncio.run(pump(True))
        overhead = {
            "off_decisions_per_sec": round(rate_off, 1),
            "on_decisions_per_sec": round(rate_on, 1),
            "throughput_retention": round(rate_on / max(rate_off, 1e-9),
                                          4),
            "harness": "in-process asyncio door (no g++; client shares "
                       "the server GIL — worst case for audit overhead)",
        }

    return {
        "trace": {"n_keys": n_keys, "n_requests": n_requests,
                  "batch": batch, "request_rate": request_rate,
                  "geometry": {"depth": depth, "width": width,
                               "sub_windows": sub_windows}},
        "sample": sample,
        "offline": {
            "false_deny_rate": round(off.false_deny_rate, 8),
            "false_allow_rate": round(off.false_allow_rate, 10),
            "cms_false_deny_rate": round(off.cms_false_deny_rate, 8),
            "semantic_disagreements": off.semantic_disagreements,
            "oracle_allows": off.oracle_allows,
        },
        "live": {
            "false_deny_rate": live_status["false_deny_rate"],
            "false_deny_wilson95": live_status["false_deny_wilson95"],
            "false_allow_rate": live_status["false_allow_rate"],
            "samples": live_status["samples"],
            "dropped_decisions": live_status["dropped_decisions"],
            "oracle_errors": live_status["oracle_errors"],
        },
        "agreement_within_wilson95": agreement,
        "door_decisions_match_offline": bool(
            np.array_equal(live_allowed, offline_allowed)),
        **({"overhead": overhead} if overhead is not None else {}),
        "wilson_note": "95% Wilson interval on the sampled false-deny "
                       "estimate; hash-coherent key sampling is a "
                       "cluster sample, so the bound treats requests as "
                       "independent (ADR-016 §2)",
        "_wilson_self_check": list(wilson_interval(
            live_status["false_denies"], live_status["oracle_allows"])),
    }


def run_hierarchy_bench(*, seconds: float = 2.0, batch: int = 4096) -> dict:
    """Hierarchical-cascade measurement (``--hierarchy``, ADR-020), two
    claims the docs make, as numbers:

    1. **One dispatch stays one dispatch**: the cascaded decision step
       (key + tenant + global scopes, tenant ids derived on device) is
       measured against the single-scope baseline on the SAME hashed
       traffic — ``cascade_ratio`` is cascade-on throughput over
       baseline (acceptance: >= 0.9 on this box).
    2. **Abuse scenarios behave, measured**: the three canonical shapes
       (evaluation/scenarios.py) run against a real cascade-enabled
       limiter; the hot-tenant storm runs with the AIMD controller and
       reports the tighten→recover trajectory plus the cascade-aware
       false-deny Wilson bound before/after the first tighten.
    """
    from ratelimiter_tpu import ManualClock, create_limiter
    from ratelimiter_tpu.core.config import HierarchySpec
    from ratelimiter_tpu.evaluation import scenarios as sc
    from ratelimiter_tpu.hierarchy import AIMDController, AIMDGains

    T0 = 1_700_000_000.0
    rng = np.random.RandomState(17)
    h64 = rng.randint(0, 1 << 63, size=batch).astype(np.uint64)

    def make_limiter(hier_spec):
        cfg = Config(
            algorithm=Algorithm.SLIDING_WINDOW, limit=1_000_000,
            window=60.0,
            sketch=SketchParams(depth=3, width=1 << 15, sub_windows=8),
            hierarchy=hier_spec)
        lim = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
        if hier_spec.enabled:
            # A populated map + registry: the kernel binary-searches a
            # real table, not an empty-array fast path.
            for j in range(6):
                lim.set_tenant(f"t{j}", 10**9, weight=j + 1)
            for i in range(256):
                lim.assign_tenant(f"key{i}", f"t{i % 6}")
        lim.allow_hashed(h64)            # warm the compile
        return lim

    def measure(lim) -> float:
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            lim.allow_hashed(h64)
            n += batch
        return n / (time.perf_counter() - t0)

    # Paired interleaved rounds: both configs sample the same host-load
    # window each round, so machine drift cancels in the ratio; the
    # reported ratio is the MEDIAN of the per-round ratios (a single
    # 2 s sample on a shared box swings ±10%).
    base_lim = make_limiter(HierarchySpec())
    casc_lim = make_limiter(HierarchySpec(tenants=8, map_capacity=1024,
                                          global_limit=10**9,
                                          default_tenant_limit=10**9))
    rounds = [(measure(base_lim), measure(casc_lim)) for _ in range(3)]
    base_lim.close()
    casc_lim.close()
    ratios = sorted(c / max(b, 1e-9) for b, c in rounds)
    ratio = ratios[len(ratios) // 2]
    base_dps = max(b for b, _ in rounds)
    casc_dps = max(c for _, c in rounds)

    # ---- hot-tenant storm (controller on) -----------------------------
    def storm_limiter():
        cfg = Config(
            algorithm=Algorithm.SLIDING_WINDOW, limit=100_000, window=60.0,
            sketch=SketchParams(depth=3, width=1 << 14, sub_windows=4),
            hierarchy=HierarchySpec(tenants=8, global_limit=1200))
        clock = ManualClock(T0)
        lim = create_limiter(cfg, backend="sketch", clock=clock)
        lim.set_tenant("attacker", 1000, weight=1, floor=50)
        lim.set_tenant("victim", 1000, weight=6, floor=50)
        for i in range(40):
            lim.assign_tenant(f"atk{i}", "attacker")
        for i in range(8):
            lim.assign_tenant(f"vic{i}", "victim")
        return lim, clock

    lim, clock = storm_limiter()
    ctl = AIMDController(
        lim, interval=999.0,
        gains=AIMDGains(decrease_factor=0.7, increase_fraction=0.2,
                        cooldown_s=0.0))
    # batch sized so baseline/recovery demand (batch × frames = 960)
    # sits under the saturation trigger (0.9 × global 1200 = 1080):
    # only the ×4 storm saturates, so the relax leg can actually engage.
    storm = sc.run_hot_tenant_storm(lim, clock, controller=ctl,
                                    batch=160, frames_per_phase=6)
    lim.close()

    # ---- rotating-key attacker vs the hh side table -------------------
    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=100_000, window=60.0,
        sketch=SketchParams(depth=3, width=1 << 14, sub_windows=4,
                            hh_slots=64),
        hierarchy=HierarchySpec(tenants=8, global_limit=10_000,
                                default_tenant_limit=200))
    clock = ManualClock(T0)
    lim = create_limiter(cfg, backend="sketch", clock=clock)
    lim.set_tenant("legit", 10_000, weight=4)
    for i in range(16):
        lim.assign_tenant(f"legit{i}", "legit")
    rotating = sc.run_rotating_key(lim, clock, batch=256, frames=8)
    lim.close()

    # ---- thundering-herd window rollover ------------------------------
    herd_weights = {"small": 1, "mid": 2, "big": 5}
    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=100_000, window=60.0,
        sketch=SketchParams(depth=3, width=1 << 14, sub_windows=4),
        hierarchy=HierarchySpec(tenants=8, global_limit=96))
    clock = ManualClock(T0)
    lim = create_limiter(cfg, backend="sketch", clock=clock)
    for name, w in herd_weights.items():
        lim.set_tenant(name, 10_000, weight=w)
        for i in range(16):
            lim.assign_tenant(f"{name}_k{i}", name)
    herd = sc.run_thundering_herd(lim, clock, tenants=herd_weights,
                                  keys_per_tenant=16, bursts_per_key=4)
    lim.close()

    ctl_block = storm.extra.get("controller", {})
    return {
        "cascade_overhead": {
            "baseline_decisions_per_sec": round(base_dps, 1),
            "cascade_decisions_per_sec": round(casc_dps, 1),
            "cascade_ratio": round(ratio, 4),
            "cascade_ratio_rounds": [round(r, 4) for r in ratios],
            "batch": batch,
            "acceptance_min_ratio": 0.9,
        },
        "scenarios": {
            "hot_tenant_storm": storm.as_dict(),
            "rotating_key": rotating.as_dict(),
            "thundering_herd": herd.as_dict(),
        },
        # The acceptance claims, as booleans the driver can grep.
        "controller_tightened_then_recovered": bool(
            ctl_block
            and ctl_block["attacker_effective_min"]
            < ctl_block["attacker_ceiling"]
            and ctl_block["attacker_effective_final"]
            == ctl_block["attacker_ceiling"]),
    }


def run_chaos_bench(scenario: str, *, n_devices: int = 4,
                    seconds: float = 2.0) -> dict:
    """Degraded-serving measurement (``--chaos``, ADR-015): arm one
    chaos scenario against a quarantine-enabled sliced mesh and measure
    the robustness contract the chaos suite proves — as NUMBERS, so
    robustness regressions become measurable like perf ones:

    * ``throughput_retention``: healthy-slice decision rate during the
      fault as a fraction of the no-fault baseline (same traffic);
    * ``quarantine_entry_latency_s``: fault armed -> victim slice out of
      routing (frames stop paying the per-slice deadline);
    * ``recovery_s``: fault cleared -> probe + rejoin complete.
    """
    import jax  # noqa: F401 — backend init after XLA_FLAGS is set

    from ratelimiter_tpu import chaos as chaos_pkg
    from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

    deadline = 0.05
    victim = 1
    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=1_000_000, window=60.0,
        fail_open=True,
        sketch=SketchParams(depth=2, width=1 << 14, sub_windows=4),
        mesh=MeshSpec(devices=n_devices, quarantine=True,
                      slice_deadline=deadline, probe_interval=0.1),
    )
    lim = SlicedMeshLimiter(cfg)
    ids = np.arange(4096, dtype=np.uint64)
    owners = lim.owner_of_id(ids)
    healthy_ids = np.ascontiguousarray(ids[owners != victim])
    for _ in range(3):  # warm every slice (and the guards' warm gates)
        lim.allow_ids(ids)

    def rate(run_ids, secs: float) -> float:
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < secs:
            lim.allow_ids(run_ids)
            n += int(run_ids.shape[0])
        return n / (time.perf_counter() - t0)

    baseline = rate(healthy_ids, seconds)

    inj = chaos_pkg.install(seed=42)
    try:
        # Arm the scenario against the victim slice. "slow-slice" delays
        # past the per-slice deadline — the canonical gray failure.
        if scenario == "slow-slice":
            inj.delay_slice(victim, 4 * deadline)
        else:
            chaos_pkg.scenario(scenario, inj, slice_idx=victim,
                               seconds=4 * deadline)
        t_arm = time.perf_counter()
        entry = float("nan")
        while time.perf_counter() - t_arm < 10.0:
            lim.allow_ids(ids)  # mixed traffic touches the victim
            if lim.quarantine.state(victim) != "healthy":
                entry = time.perf_counter() - t_arm
                break
        degraded = rate(healthy_ids, seconds)
        degraded_mixed = rate(ids, max(0.5, seconds / 2))
        inj.clear_slice(victim)
        t_clear = time.perf_counter()
        recovery = float("nan")
        while time.perf_counter() - t_clear < 30.0:
            lim.allow_ids(ids)  # traffic kicks the lazy half-open probe
            if lim.quarantine.state(victim) == "healthy":
                recovery = time.perf_counter() - t_clear
                break
            time.sleep(0.01)
        status = lim.quarantine.status()
    finally:
        chaos_pkg.uninstall()
        lim.close()
    def _num(x, nd):
        # null, never NaN: json.dumps renders bare NaN, which strict
        # JSON parsers reject — exactly when the regression this block
        # exists to catch (no quarantine entry / no recovery) happened.
        return None if x != x else round(x, nd)

    return {
        "scenario": scenario,
        "n_devices": n_devices,
        "victim_slice": victim,
        "slice_deadline_s": deadline,
        "baseline_healthy_rate": round(baseline, 1),
        "degraded_healthy_rate": round(degraded, 1),
        "throughput_retention": round(degraded / max(baseline, 1e-9), 3),
        "degraded_mixed_rate": round(degraded_mixed, 1),
        "quarantine_entry_latency_s": _num(entry, 4),
        "recovery_s": _num(recovery, 4),
        "degraded_decisions": status["degraded_decisions"],
        "transitions": status["transitions"],
    }


def main() -> None:
    import jax
    import jax.numpy as jnp

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chaos", default=None, metavar="SCENARIO",
                    help="run ONLY the degraded-serving chaos bench "
                         "(ADR-015) for this scenario (slow-slice, "
                         "kill-slice, wedge-slice) and emit a "
                         "degraded_serving JSON block")
    ap.add_argument("--hierarchy", action="store_true",
                    help="run ONLY the hierarchical-cascade bench "
                         "(ADR-020) and emit a hierarchy JSON block: "
                         "cascade-on vs single-scope throughput on the "
                         "same hashed traffic (one-dispatch claim), "
                         "plus the three abuse scenarios measured "
                         "against a real cascade — hot-tenant storm "
                         "with the AIMD tighten→recover trajectory and "
                         "cascade-aware false-deny Wilson bounds, "
                         "rotating-key containment, thundering-herd "
                         "fair-share clipping")
    ap.add_argument("--audit", action="store_true",
                    help="run ONLY the live accuracy observatory bench "
                         "(ADR-016) and emit a live_accuracy JSON "
                         "block: measured audit-on/off overhead A/B "
                         "plus agreement of the live hash-sampled "
                         "estimate with the offline three-way oracle "
                         "ground truth on a seeded trace")
    ap.add_argument("--audit-sample", type=int, default=64, metavar="N",
                    help="--audit: audit 1 in N of the keyspace "
                         "(hash-coherent)")
    ap.add_argument("--snapshot-interval", type=float, default=None,
                    metavar="S",
                    help="also measure durability overhead (phase E): "
                         "p50/p99 allow latency with a background "
                         "snapshotter at this interval vs bare")
    ap.add_argument("--inflight", type=int, default=8, metavar="N",
                    help="pipelined dispatch window for the phase-D "
                         "server (1 = the old synchronous path)")
    ap.add_argument("--trace", action="store_true",
                    help="also run the flight-recorder stage breakdown "
                         "(ADR-014): a traced in-process serving run "
                         "reduced to per-stage mean/p99 microseconds "
                         "(stage_us block in the JSON)")
    ap.add_argument("--mesh-devices", type=int, default=None, metavar="N",
                    help="also sweep the slice-parallel mesh backend "
                         "(ADR-012) at n=1,2,4,..,N devices and emit the "
                         "multichip_scaling curve (device step rate + e2e "
                         "serving rate per count). On CPU this forces N "
                         "virtual host devices")
    ap.add_argument("--router", default="host",
                    choices=["host", "collective"],
                    help="--mesh-devices: 'collective' ALSO serves every "
                         "e2e row through the collective mesh router "
                         "(ADR-024, --router collective servers — one "
                         "shard_map dispatch per frame, zero host "
                         "partitioning) and adds the e2e_collective_* "
                         "columns plus the route_phase_us host-phase "
                         "breakdown; host rows are always measured (the "
                         "comparison is the point)")
    ap.add_argument("--accel", action="store_true",
                    help="run ONLY the real-accelerator proof preset "
                         "(ROADMAP item 5) and emit one JSON: the "
                         "--inflight pipelining sweep, the mesh scaling curve (affine AND "
                         "mixed) through BOTH routers, and the "
                         "route-phase breakdown. Auto-detects the "
                         "platform; also writes the JSON to "
                         "BENCH_<platform>_r01.json (override with "
                         "BENCH_ACCEL_OUT=path; devices via "
                         "--mesh-devices, default 8)")
    ap.add_argument("--fleet-hosts", type=int, default=None, metavar="N",
                    help="run ONLY the fleet scale-out bench (ADR-017, "
                         "forward lanes ADR-019) and emit the "
                         "fleet_scaling JSON block: single-host "
                         "baseline, then affine + mixed rows at 2 AND "
                         "N hosts (N > 2 adds the routing-vs-N^2-"
                         "chatter row: per-host mixed throughput "
                         "should stay flat), expected vs measured "
                         "forwarded fraction over GO-aligned windows, "
                         "and the kill -9 failover row (the multi-HOST "
                         "sibling of --mesh-devices' multichip_scaling)")
    ap.add_argument("--fleet-obs", action="store_true",
                    help="run ONLY the all-observability-on fleet "
                         "retention bench (ADR-021) and emit the "
                         "fleet_obs JSON block: 2-host mixed traffic, "
                         "INTERLEAVED off/on pairs (flight recorder + "
                         "audit + hh + event journal + tower surfaces "
                         "scraped mid-run vs everything off), best "
                         "paired retention ratio; bar >= 0.97 "
                         "(published as OBS_r01.json)")
    ap.add_argument("--leases", action="store_true",
                    help="run ONLY the client-embedded lease bench "
                         "(ADR-022) and emit the leases JSON block: "
                         "client-observed decision rate on hot-key "
                         "traffic leased vs wire against one real "
                         "server (bar >= 5x), the never-over-admit "
                         "oracle through a seeded revocation storm "
                         "(bit-exact), the observatory's Wilson-"
                         "bounded false-deny delta leases on vs off, "
                         "and the leases-off byte-identical pin "
                         "(published as LEASE_r01.json)")
    ap.add_argument("--rebalance", action="store_true",
                    help="run ONLY the load-aware placement bench "
                         "(ADR-023) over a 3-member fleet and emit the "
                         "rebalance JSON block: skewed-hotspot "
                         "imbalance before/after an operator "
                         "dry-run -> apply through the bearer door "
                         "(bar: >= 2.0x converging to <= 1.3x), the "
                         "per-key admission oracle across the wire "
                         "handoff (zero over-admission, zero client "
                         "errors), the one-correlation-id journal "
                         "reconstruction, and the rebalance-off "
                         "byte-identical pin (published as "
                         "REBALANCE_r01.json)")
    ap.add_argument("--shm", action="store_true",
                    help="run ONLY the shared-memory wire-lane A/B "
                         "(ADR-025) and emit the shm_transport JSON "
                         "block: interleaved paired tcp-loopback / uds "
                         "/ shm rounds through the C++ loadgen's "
                         "hashed lane against real --native --shm "
                         "servers, best paired ratios + per-frame "
                         "serialize/wire-write phase breakdown, plus "
                         "the single-device step rate so the "
                         "device-vs-e2e gap is tracked per transport "
                         "(published as SHM_r01.json)")
    ap.add_argument("--conn-sweep", action="store_true",
                    help="run ONLY the network-engine connection sweep "
                         "(ISSUE-20, ADR-026) and emit the neteng JSON "
                         "block: interleaved paired rounds of the "
                         "pre-PR single-epoll write-per-frame baseline "
                         "vs the multi-ring engine at 16..512 tcp "
                         "connections through the C++ loadgen, per-row "
                         "throughput, p99, and syscalls-per-decision "
                         "from engine counter deltas (published as "
                         "NETENG_r01.json)")
    ap.add_argument("--reshard", action="store_true",
                    help="run ONLY the elastic lifecycle bench "
                         "(ADR-018) over a 2-host fleet and emit the "
                         "reshard JSON block: migration window on a "
                         "SIGTERM departure handoff, e2e retention + "
                         "client errors through a full rolling restart "
                         "of one member, automatic rejoin convergence "
                         "time, and offline tools/rebucket.py resize "
                         "timings (published as RESHARD_r01.json)")
    args = ap.parse_args()

    if args.shm:
        from benchmarks.e2e import run_shm_ab

        platform = jax.devices()[0].platform
        payload = {
            "metric": "shm_transport",
            "platform": platform,
            "shm_transport": run_shm_ab(
                seconds=float(os.environ.get("BENCH_SECONDS", "4")),
                pairs=int(os.environ.get("BENCH_SHM_PAIRS", "3")),
                log=lambda *a: print(*a, file=sys.stderr, flush=True)),
        }
        st = payload["shm_transport"]
        if "error" not in st:
            # The device-vs-e2e gap per transport: the shm lane's claim
            # is a smaller wire tax between those two numbers.
            dev = measure_mesh_step_rate(
                1, seconds=float(os.environ.get("BENCH_MESH_SECONDS",
                                                "2")))
            st["device_step_decisions_per_sec"] = round(dev, 1)
            for t in ("shm", "uds"):
                e2e = float(st["paired_best"][t]["decisions_per_sec"])
                st["paired_best"][t]["device_gap"] = round(
                    dev / max(e2e, 1.0), 2)
            tcp_e2e = float(
                st["paired_best"]["shm"]["tcp_decisions_per_sec"])
            st["tcp_device_gap"] = round(dev / max(tcp_e2e, 1.0), 2)
        out_path = os.environ.get("BENCH_SHM_OUT", "SHM_r01.json")
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(json.dumps(payload))
        return

    if args.conn_sweep:
        from benchmarks.e2e import run_conn_sweep

        conns = tuple(int(x) for x in os.environ.get(
            "BENCH_NETENG_CONNS", "16,64,256,512").split(","))
        payload = {
            "metric": "neteng_conn_sweep",
            "platform": jax.devices()[0].platform,
            "neteng": run_conn_sweep(
                seconds=float(os.environ.get("BENCH_SECONDS", "2.5")),
                pairs=int(os.environ.get("BENCH_NETENG_PAIRS", "2")),
                conns=conns,
                log=lambda *a: print(*a, file=sys.stderr, flush=True)),
        }
        out_path = os.environ.get("BENCH_NETENG_OUT", "NETENG_r01.json")
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(json.dumps(payload))
        return

    if args.rebalance:
        from benchmarks.rebalance import run_rebalance

        print(json.dumps({
            "metric": "rebalance",
            "platform": jax.devices()[0].platform,
            "rebalance": run_rebalance(
                seconds=float(os.environ.get("BENCH_SECONDS", "4")),
                log=lambda *a: print(*a, file=sys.stderr)),
        }))
        return

    if args.reshard:
        # Before the first jax.devices() call initializes the backend:
        # the offline rebucket row builds a 4-slice mesh in-process.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
        from benchmarks.reshard import run_reshard

        print(json.dumps({
            "metric": "reshard",
            "platform": jax.devices()[0].platform,
            "reshard": run_reshard(
                seconds=float(os.environ.get("BENCH_SECONDS", "4")),
                log=lambda *a: print(*a, file=sys.stderr)),
        }))
        return

    if args.fleet_obs:
        from benchmarks.obs import run_fleet_obs

        print(json.dumps({
            "metric": "fleet_obs",
            "platform": jax.devices()[0].platform,
            "fleet_obs": run_fleet_obs(
                seconds=float(os.environ.get("BENCH_SECONDS", "4")),
                pairs=int(os.environ.get("BENCH_OBS_PAIRS", "3")),
                log=lambda *a: print(*a, file=sys.stderr)),
        }))
        return

    if args.leases:
        from benchmarks.leases import run_leases

        print(json.dumps({
            "metric": "leases",
            "platform": jax.devices()[0].platform,
            "leases": run_leases(
                seconds=float(os.environ.get("BENCH_SECONDS", "4")),
                log=lambda *a: print(*a, file=sys.stderr)),
        }))
        return

    if args.fleet_hosts:
        from benchmarks.fleet import run_fleet_scaling

        print(json.dumps({
            "metric": "fleet_scaling",
            "platform": jax.devices()[0].platform,
            "fleet_scaling": run_fleet_scaling(
                max(2, args.fleet_hosts),
                seconds=float(os.environ.get("BENCH_SECONDS", "4")),
                log=lambda *a: print(*a, file=sys.stderr)),
        }))
        return

    if args.hierarchy:
        print(json.dumps({
            "metric": "hierarchy",
            "platform": jax.devices()[0].platform,
            "hierarchy": run_hierarchy_bench(
                seconds=float(os.environ.get("BENCH_SECONDS", "2.0"))),
        }))
        return

    if args.audit:
        platform = jax.devices()[0].platform
        quick = platform == "cpu"
        print(json.dumps({
            "metric": "live_accuracy",
            "platform": platform,
            "live_accuracy": measure_live_accuracy(
                sample=args.audit_sample,
                n_keys=20_000 if quick else 200_000,
                n_requests=int(os.environ.get("BENCH_AUDIT_REQUESTS",
                                              "120000" if quick
                                              else "600000")),
                overhead_seconds=float(os.environ.get(
                    "BENCH_AUDIT_SECONDS", "4.0"))),
        }))
        return

    if args.chaos:
        # Before any jax.devices() call initializes the backend (same
        # ordering rule as --mesh-devices below). A pre-set device-count
        # flag wins: size the mesh to it instead of assuming 4.
        import re as _re

        flags = os.environ.get("XLA_FLAGS", "")
        m = _re.search(r"xla_force_host_platform_device_count=(\d+)",
                       flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
            n_dev = 4
        else:
            n_dev = max(2, int(m.group(1)))
        print(json.dumps({
            "metric": "degraded_serving",
            "platform": jax.devices()[0].platform,
            "degraded_serving": run_chaos_bench(args.chaos,
                                                n_devices=n_dev),
        }))
        return

    if args.mesh_devices or args.accel:
        # Must land before the first jax.devices() call initializes the
        # backend; on real accelerators the flag only affects the (then
        # unused) host platform. Spawned e2e servers inherit it via env.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.mesh_devices or 8}").strip()

    if args.accel:
        avail = len(jax.devices())
        counts = [1]
        while counts[-1] * 2 <= min(args.mesh_devices or 8, avail):
            counts.append(counts[-1] * 2)
        payload = {
            "metric": "accel_preset",
            **run_accel_preset(
                counts,
                seconds=float(os.environ.get("BENCH_MESH_SECONDS", "3")),
                e2e_seconds=float(os.environ.get("BENCH_SECONDS", "4")),
                log=lambda msg: print(msg, file=sys.stderr, flush=True)),
        }
        out_path = os.environ.get(
            "BENCH_ACCEL_OUT",
            f"BENCH_{payload['platform']}_r01.json")
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(json.dumps(payload))
        return

    platform = jax.devices()[0].platform
    on_accel = platform != "cpu"
    B = (1 << 22) if on_accel else (1 << 16)
    n_keys = N_KEYS if on_accel else 50_000
    # Default >= 1.0 window of coverage on a real chip: steady-state error
    # is reached once the full 60 s window has filled, so partial coverage
    # understates false-deny (VERDICT r3 weak item 4). CPU fallback keeps a
    # tiny default so the suite smoke stays fast.
    acc_windows = float(os.environ.get("BENCH_ACC_WINDOWS",
                                       "1.25" if on_accel else "0.02"))
    bench_seconds = float(os.environ.get("BENCH_SECONDS", "6"))

    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW,
        limit=100,
        window=60.0,
        max_batch_admission_iters=1,   # exact for uniform n==1 (segment.py)
        sketch=SketchParams(depth=3, width=1 << (20 if on_accel else 14),
                            sub_windows=60, conservative_update=True),
    )
    _, sub_us, _, _, _ = sketch_kernels.sketch_geometry(cfg)
    _, sk_roll = sketch_kernels.build_controls(cfg)

    # ---------------------------------------------- phase A: throughput
    chunk = build_bench_chunk(cfg, B, n_keys, ZIPF_A)
    state = sk_roll(sketch_kernels.init_state(cfg), jnp.int64(T0_US // sub_us))

    t0 = time.perf_counter()
    state, packed, _ = chunk(state, jnp.uint64(0), jnp.int64(T0_US))
    _sync(packed)
    compile_a = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(1, 4):
        state, packed, _ = chunk(state, jnp.uint64(i * B), jnp.int64(T0_US))
    _sync(packed)
    est_rate = 3 * B / (time.perf_counter() - t0)

    n_chunks = max(4, min(int(bench_seconds * est_rate / B), 256))
    period = T0_US // sub_us
    denies = []
    ctr = 4 * B
    t0 = time.perf_counter()
    for i in range(n_chunks):
        t_virt = T0_US + int((i + 1) * B / est_rate * 1e6)
        p = t_virt // sub_us
        if p > period:
            state = sk_roll(state, jnp.int64(p))
            period = p
        state, packed, dn = chunk(state, jnp.uint64(ctr), jnp.int64(t_virt))
        denies.append(dn)
        ctr += B
    denied = int(np.asarray(jnp.sum(jnp.stack(denies))))
    elapsed = time.perf_counter() - t0
    decisions = n_chunks * B
    rps = decisions / elapsed
    del state, packed, denies

    # ---------------------------------------------- phase B: accuracy
    eval_chunk = build_eval_chunk(cfg, B, n_keys, ZIPF_A)
    or_roll = build_oracle_rollover(cfg, n_keys)
    states = {"sk": sk_roll(sketch_kernels.init_state(cfg),
                            jnp.int64(T0_US // sub_us)),
              "or": or_roll(init_oracle_state(cfg, n_keys),
                            jnp.int64(T0_US // sub_us))}
    t0 = time.perf_counter()
    states, stats = eval_chunk(states, jnp.uint64(0), jnp.int64(T0_US))
    _sync(stats[0])
    compile_b = time.perf_counter() - t0

    # Cap like phase A: each eval chunk is ~2x a phase-A chunk of work, so an
    # uncapped count would make the accuracy phase's wall time unbounded on a
    # fast chip. The achieved (possibly reduced) coverage is reported below.
    acc_chunks = max(2, min(int(acc_windows * cfg.window * rps / B), 512))
    period = T0_US // sub_us
    acc = []
    ctr = B
    for i in range(acc_chunks):
        t_virt = T0_US + int((i + 1) * B / rps * 1e6)
        p = t_virt // sub_us
        if p > period:
            states = {"sk": sk_roll(states["sk"], jnp.int64(p)),
                      "or": or_roll(states["or"], jnp.int64(p))}
            period = p
        states, stats = eval_chunk(states, jnp.uint64(ctr), jnp.int64(t_virt))
        acc.append(jnp.stack(stats))
        ctr += B
    fd, fa, sk_deny, or_deny = [int(x) for x in
                                np.asarray(jnp.sum(jnp.stack(acc), axis=0))]
    acc_decisions = acc_chunks * B
    or_allowed = acc_decisions - or_deny
    coverage = acc_chunks * B / rps / cfg.window
    del states, acc

    # Three-way error split (ADR-016 satellite): phase B above measures
    # the COMBINED false-deny/false-allow rates at full scale on-device;
    # this companion runs the shared comparison engine
    # (evaluation/compare.py — the same code the live auditor runs) at
    # CI scale with a collision-free twin, separating the pure-CMS
    # collision component from the sub-window-vs-two-window semantic
    # component, so the bench JSON finally says WHICH error source moved
    # when the combined rate does.
    from ratelimiter_tpu.evaluation import evaluate_accuracy

    # Width 2^10 against ~16K active keys: collisions measurably bite
    # (fd ~2e-3 at full trace length), so the split has events to
    # attribute — a zero/zero split would say nothing.
    three = evaluate_accuracy(
        n_keys=20_000, n_requests=120_000 if on_accel else 60_000,
        batch=4096, limit=50, window=60.0, request_rate=50_000.0,
        sketch=SketchParams(depth=3, width=1 << 10, sub_windows=30,
                            conservative_update=True))
    three_way = {
        "note": "shared engine (evaluation/compare.py) at CI scale — "
                "attribution of the error SPLIT, not the at-scale rate "
                "(which phase B above measures)",
        "false_deny_rate": round(three.false_deny_rate, 6),
        "false_deny_wilson95": [round(v, 6)
                                for v in three.false_deny_wilson95],
        "cms_false_deny_rate": round(three.cms_false_deny_rate, 6),
        "cms_false_denies_vs_twin": three.cms_false_denies_vs_twin,
        "false_denies_vs_oracle": three.false_denies_vs_oracle,
        "semantic_disagreements": three.semantic_disagreements,
        "requests": three.requests,
    }

    # ---------------------------------------------- phase C: serving shape
    # K pipelined dispatches per sync: r4 used K=8 and the sync overhead
    # alone kept the captured number at 7.7M/s (469 us/step) on the same
    # kernels — the ceiling was always there, the harness just didn't
    # amortize the per-sync round trip; larger K converges on the
    # steady-state step time (not measured on this round's code). CPU smoke
    # keeps a small K (its ~7 ms/step would make 128 dispatches take
    # a minute).
    K = 128 if on_accel else 4
    from ratelimiter_tpu.ops.hashing import split_hash, splitmix64

    def serve_shape(scfg, warm_state_roll):
        scan = sketch_kernels.build_scan(scfg)
        _, s_sub, _, _, _ = sketch_kernels.sketch_geometry(scfg)
        st = warm_state_roll(sketch_kernels.init_state(scfg),
                             jnp.int64(T0_US // s_sub))
        rng = np.random.default_rng(0)
        ids = rng.zipf(ZIPF_A, size=(SCAN_STEPS, INGEST_BATCH)
                       ).astype(np.uint64)
        h1, h2 = split_hash(splitmix64(ids.reshape(-1)), scfg.sketch.seed)
        h1s = jnp.asarray(h1.reshape(SCAN_STEPS, INGEST_BATCH))
        h2s = jnp.asarray(h2.reshape(SCAN_STEPS, INGEST_BATCH))
        ns_t = jnp.ones((SCAN_STEPS, INGEST_BATCH), jnp.int32)
        dt_us = 400  # 2.5K ingest batches/s; 64 steps stay in one sub-window
        t0 = time.perf_counter()
        st, masks, _ = scan(st, h1s, h2s, ns_t, jnp.int64(T0_US),
                            jnp.int64(dt_us))
        _sync(masks)
        comp = time.perf_counter() - t0
        # RTT audit (ISSUE-4 satellite): the FIRST post-compile dispatch
        # still pays one-time costs (executable upload, donation-buffer
        # setup), so it is reported separately as cold; the warm
        # figure is the min of several steady-state round trips and is
        # what dispatch_rtt_ms now means.
        t0 = time.perf_counter()
        st, masks, _ = scan(st, h1s, h2s, ns_t,
                            jnp.int64(T0_US + SCAN_STEPS * dt_us),
                            jnp.int64(dt_us))
        _sync(masks)
        rtt_cold = time.perf_counter() - t0
        warm = []
        for j in range(3):
            t0 = time.perf_counter()
            st, masks, _ = scan(st, h1s, h2s, ns_t,
                                jnp.int64(T0_US + (2 + j) * SCAN_STEPS
                                          * dt_us),
                                jnp.int64(dt_us))
            _sync(masks)
            warm.append(time.perf_counter() - t0)
        rtt_warm = min(warm)
        t0 = time.perf_counter()
        for i in range(K):
            now0 = T0_US + (5 + i) * SCAN_STEPS * dt_us
            st, masks, _ = scan(st, h1s, h2s, ns_t, jnp.int64(now0),
                                jnp.int64(dt_us))
        _sync(masks)
        per_scan = (time.perf_counter() - t0) / K
        return (SCAN_STEPS * INGEST_BATCH / per_scan,
                per_scan / SCAN_STEPS * 1e3, rtt_warm, rtt_cold, comp)

    # Headline: the LITERAL BASELINE config-3 geometry (the spec'd
    # serving shape). Secondary: the wide geometry phases A/B measure
    # accuracy at, so both doctrines are captured in one artifact.
    lit_cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=100, window=60.0,
        max_batch_admission_iters=1,
        sketch=SketchParams(depth=4, width=1 << 16, sub_windows=60,
                            conservative_update=True))
    _, lit_roll = sketch_kernels.build_controls(lit_cfg)
    serving_rps, step_latency_ms, rtt_warm_s, rtt_cold_s, compile_c = (
        serve_shape(lit_cfg, lit_roll))
    wide_rps, wide_step_ms, _, _, compile_c2 = serve_shape(cfg, sk_roll)
    compile_c += compile_c2

    # Host-phase breakdown (ISSUE-4 satellite): string vs hashed wire
    # path host cost per frame, independent of the device.
    host_phases = measure_host_phases()

    # ---------------------------------------------- phase D: e2e serving
    # The native C++ loadgen measures the SERVER (the Python asyncio
    # driver bottlenecks on its own event loop and measures the
    # CLIENT); fall back to it only without g++.
    e2e: dict = {}
    try:
        import shutil

        if shutil.which("g++"):
            from benchmarks.e2e import _run_native_loadgen

            # 6 s timed window: on a small box the number is sensitive
            # to scheduler state; the longer window cuts run-to-run
            # variance.
            row = _run_native_loadgen(seconds=6.0, log=lambda *a: None,
                                      inflight=args.inflight)
            if "error" in row:
                raise RuntimeError(row["error"])
            pipelined = args.inflight > 1
            e2e = {
                "e2e_server_decisions_per_sec": row["decisions_per_sec"],
                "e2e_inflight": args.inflight,
                "e2e_frame_p50_ms": row["frame_p50_ms"],
                "e2e_frame_p99_ms": row["frame_p99_ms"],
                # --inflight 1 is the synchronous A/B baseline (EXAMPLES
                # §16): the pipelined field/label must not claim it.
                "e2e_server_front_door": (
                    "native (pipelined launch/resolve, ADR-010)"
                    if pipelined else "native (synchronous, --inflight 1)"),
                "e2e_harness": "cpp_loadgen (6 conns x 8 pipelined "
                               "1024-key frames; latency is per frame)",
            }
            if pipelined:
                e2e["e2e_pipelined_decisions_per_sec"] = (
                    row["decisions_per_sec"])
            # The zero-copy hashed lane (ALLOW_HASHED raw u64 ids,
            # device-side hashing, ADR-011), same server shape — the
            # string/hashed delta is the wire path's contribution.
            hrow = _run_native_loadgen(seconds=6.0, log=lambda *a: None,
                                       inflight=args.inflight, hashed=True)
            if "error" not in hrow:
                e2e["e2e_hashed_decisions_per_sec"] = (
                    hrow["decisions_per_sec"])
                e2e["e2e_hashed_frame_p50_ms"] = hrow["frame_p50_ms"]
                e2e["e2e_hashed_frame_p99_ms"] = hrow["frame_p99_ms"]
        else:
            from benchmarks.e2e import _drive, _spawn_server
            import asyncio

            proc, port = _spawn_server("sketch", platform="cpu",
                                       max_batch=4096, max_delay_us=500.0)
            try:
                e2e_out = asyncio.run(_drive(port, seconds=4.0, conns=4,
                                             window=2048, n_keys=100_000))
                e2e = {
                    "e2e_server_decisions_per_sec":
                        e2e_out["decisions_per_sec"],
                    "e2e_server_scalar_p50_ms": e2e_out["scalar_p50_ms"],
                    "e2e_server_scalar_p99_ms": e2e_out["scalar_p99_ms"],
                    "e2e_server_front_door": "asyncio",
                    "e2e_harness": "python_asyncio_clients (client-bound; "
                                   "no g++ for the real harness)",
                }
            finally:
                proc.terminate()
                proc.wait(timeout=15)
    except Exception as exc:  # report the omission, never fail the bench
        e2e = {"e2e_server_error": str(exc)[:200]}
    if "e2e_server_decisions_per_sec" in e2e:
        # The gap this PR chips at (ISSUE-4): raw device step rate over
        # the rate actually served through the front door. 1.0 means the
        # host/wire path costs nothing. (Here the served leg runs on
        # the CPU device, so the ratio compares two devices; S1.)
        e2e["e2e_device_gap"] = round(
            serving_rps / max(float(e2e["e2e_server_decisions_per_sec"]),
                              1.0), 2)

    # -------------------------------------- phase F: multichip scaling
    # (opt-in, --mesh-devices N): the slice-parallel mesh backend's
    # scaling curve — device step rate and e2e served rate at each
    # device count, plus the per-count e2e_device_gap (ISSUE-5). The
    # single-device JSON schema above is unchanged; this adds one key.
    mesh_block: dict = {}
    if args.mesh_devices:
        avail = len(jax.devices())
        counts = [1]
        while counts[-1] * 2 <= min(args.mesh_devices, avail):
            counts.append(counts[-1] * 2)
        routers = (("host", "collective") if args.router == "collective"
                   else ("host",))
        mesh_block = {"multichip_scaling": measure_mesh_scaling(
            counts, seconds=float(os.environ.get("BENCH_MESH_SECONDS", "3")),
            e2e_seconds=4.0, routers=routers,
            log=lambda msg: print(msg, file=sys.stderr, flush=True))}
        if args.router == "collective":
            # The "host partitioning eliminated" evidence (ADR-024):
            # per-frame host-phase microseconds for both routers.
            mesh_block["route_phase_us"] = measure_route_phases(
                n=counts[-1])

    # --------------------------------------- phase G: stage attribution
    # (opt-in, --trace): per-stage latency breakdown from the flight
    # recorder over a traced in-process serving run (ADR-014).
    trace_block: dict = {}
    if args.trace:
        trace_block = {"trace_stage_breakdown": measure_stage_breakdown(
            seconds=1.5 if not on_accel else 3.0)}

    # ------------------------------------------ phase E: durability cost
    snap_overhead: dict = {}
    if args.snapshot_interval is not None:
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            snap_overhead = {"snapshot_overhead": measure_snapshot_overhead(
                args.snapshot_interval, snapshot_dir=d,
                seconds=2.0 if on_accel else 1.0,
                width=(1 << 18) if on_accel else (1 << 14))}

    print(json.dumps({
        "metric": "sketch_allow_decisions_per_sec",
        "value": round(rps, 1),
        "unit": "decisions/sec",
        "vs_baseline": round(rps / REFERENCE_SLIDING_WINDOW_RPS, 2),
        "vs_north_star": round(rps / NORTH_STAR_RPS, 3),
        "decisions": decisions,
        "device_batch": B,
        "deny_fraction": round(denied / max(decisions, 1), 4),
        "false_deny_rate_vs_oracle": round(fd / max(or_allowed, 1), 6),
        "false_allow_rate_vs_oracle": round(fa / max(or_deny, 1), 9),
        "accuracy_decisions": acc_decisions,
        "accuracy_window_coverage": round(coverage, 3),
        # Why coverage matters (r3 measured 0.043% at 0.25 coverage, r4
        # 0.83% at 1.25): error GROWS as the window fills with admitted
        # mass, so only >= 1.0-window coverage is steady state — the two
        # numbers measure different operating points, not a regression.
        "accuracy_note": "steady-state (>=1x window filled); partial "
                         "coverage understates false-deny",
        # The accuracy geometry's sizing doctrine, CHECKED in-run: the
        # measured admitted in-window mass vs SketchParams.mass_budget
        # (the for_load sizing anchor).
        "accuracy_geometry_doctrine": (
            "for_load-consistent: admitted in-window mass within the "
            "geometry's calibrated budget"
            if (acc_decisions - sk_deny) / max(coverage, 1e-9)
            <= cfg.sketch.mass_budget(cfg.limit)
            else "OVER mass budget: geometry undersized for this load"),
        "accuracy_admitted_mass_per_window": int(
            (acc_decisions - sk_deny) / max(coverage, 1e-9)),
        "accuracy_mass_budget": cfg.sketch.mass_budget(cfg.limit),
        "accuracy_three_way": three_way,
        "serving_ingest_batch": INGEST_BATCH,
        "serving_scan_steps": SCAN_STEPS,
        "serving_pipelined_dispatches": K,
        "serving_decisions_per_sec": round(serving_rps, 1),
        "serving_step_latency_ms": round(step_latency_ms, 3),
        "serving_geometry": {
            "depth": lit_cfg.sketch.depth, "width": lit_cfg.sketch.width,
            "sub_windows": lit_cfg.sketch.sub_windows,
            "conservative_update": lit_cfg.sketch.conservative_update},
        "serving_sizing_doctrine": "literal BASELINE config 3 "
                                   "(d=4 w=65536, the spec'd shape)",
        "serving_decisions_per_sec_wide_geometry": round(wide_rps, 1),
        "serving_step_latency_ms_wide_geometry": round(wide_step_ms, 3),
        # Warm steady-state dispatch RTT (min of 3 post-warm-up scans);
        # the first post-compile dispatch's one-time costs are reported
        # separately as cold.
        "dispatch_rtt_ms": round(rtt_warm_s * 1e3, 1),
        "dispatch_rtt_cold_ms": round(rtt_cold_s * 1e3, 1),
        "host_phase_us": host_phases,
        "compile_s": round(compile_a + compile_b + compile_c, 1),
        "platform": platform,
        "sketch_geometry": {"depth": cfg.sketch.depth, "width": cfg.sketch.width,
                            "sub_windows": 60, "conservative_update": True},
        **e2e,
        **mesh_block,
        **snap_overhead,
        **trace_block,
    }))


if __name__ == "__main__":
    main()
