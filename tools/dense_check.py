#!/usr/bin/env python3
"""The dense backend's device directory against the plain rule, at the
published size, at the library level with an explicit clock (ISSUE 33,
item c).

    chiprun -- python3 tools/dense_check.py            # the chip
    JAX_PLATFORMS=cpu python3 tools/dense_check.py     # rehearsal

On the chip it runs at the size of ``exact-tb-1m`` (token bucket, burst
100, refill 100 / 60 s, 1,000,000 keys, a directory of 2**21 entries);
with ``JAX_PLATFORMS=cpu`` it is a rehearsal at 4,096 keys in 16,384
entries (counts only, exit 3; ``--published`` rehearses the published
size). One process, outside any timed window.

Seeded frames of the cell's own traffic — Zipf(1.1) over the population
through a fixed rank -> id permutation, 4,096 ids a frame with in-frame
duplicates, and a few frames of 8 and 1,003 — go through
``DenseLimiter.launch_ids`` / ``resolve`` with four tickets in flight, at
instants that advance so that buckets refill in part and in whole, and,
the same requests in the same order, through ``algorithms/exact.py``'s
``ExactLimiter`` (a Python dict and integers; no JAX). Every result
column of every frame — ``allowed``, ``remaining``, ``retry_after``,
``reset_at`` — must be equal, no tolerance: the arithmetic is integer.

Bounds: 0 columns differ; no row unplaced; the directory holds each
distinct id once (read back from the device); one fetch a dispatch.
Reported beside them: the directory's counts (probes a lookup, inserts,
load), and — ``--timed N`` — the wall time of N more pipelined frames
(host clock; a rate only on the chip). ``--profile DIR`` captures those
frames with ``jax.profiler`` and prints the device's op groups through
``chipbench/trace_reduce.py``.

The last stdout line is one JSON object with the counts; exit 0 when
every bound holds, 1 when one does not, 3 for a passed rehearsal.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

T0 = 1_700_000_000.0
COLUMNS = ("allowed", "remaining", "retry_after", "reset_at")
LIMIT, WINDOW_S, ZIPF_S, FRAME, INFLIGHT = 100, 60.0, 1.1, 4096, 4
#: exact-tb-1m's size, and the rehearsal's.
CHIP = dict(keys=1_000_000, capacity=1 << 21)
SMALL = dict(keys=4096, capacity=1 << 14)


def zipf_ranks(rng, n_keys: int, count: int) -> np.ndarray:
    """``count`` ranks in [0, n_keys) with P(rank r) ~ (r + 1)**-ZIPF_S."""
    weights = np.arange(1, n_keys + 1, dtype=np.float64) ** -ZIPF_S
    cdf = np.cumsum(weights)
    return np.searchsorted(cdf, rng.random(count) * cdf[-1]).astype(np.int64)


def frames_of(seed: int, n_keys: int, n_frames: int):
    """[(ids uint64[b], seconds to advance after the frame)]."""
    rng = np.random.default_rng(seed)
    perm = np.random.default_rng(12).permutation(n_keys).astype(np.uint64)
    sizes = [FRAME] * n_frames + [8, 1003, 8, 1003]
    rng.shuffle(sizes)
    # Mostly a few milliseconds apart, as served frames are; now and then
    # a stretch in which a drained bucket refills in part, once a whole
    # window.
    gaps = rng.choice([0.0, 0.002, 0.004, 0.7, 9.0], size=len(sizes),
                      p=[0.2, 0.4, 0.3, 0.07, 0.03])
    gaps[len(sizes) // 2] = WINDOW_S + 1.0
    return [(perm[zipf_ranks(rng, n_keys, b)], float(g))
            for b, g in zip(sizes, gaps)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483033)
    ap.add_argument("--frames", type=int, default=16,
                    help="frames of 4,096 ids held to the plain rule")
    ap.add_argument("--published", action="store_true",
                    help="the published size under JAX_PLATFORMS=cpu too")
    ap.add_argument("--timed", type=int, default=0, metavar="N",
                    help="then N more frames, timed (not compared)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture the timed frames with jax.profiler")
    args = ap.parse_args(argv)

    from ratelimiter_tpu.core import jaxcfg

    jaxcfg.configure()
    import jax

    from ratelimiter_tpu import (
        Algorithm,
        Config,
        DenseParams,
        ManualClock,
        create_limiter,
    )
    from ratelimiter_tpu.ops import directory
    from ratelimiter_tpu.ops.hashing import splitmix64

    dev = jax.devices()[0]
    rehearsal = dev.platform == "cpu"
    size = CHIP if (args.published or not rehearsal) else SMALL
    clock = ManualClock(T0)
    cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=LIMIT,
                 window=WINDOW_S,
                 dense=DenseParams(capacity=size["capacity"]))
    dense = create_limiter(cfg, backend="dense", clock=clock)
    exact = create_limiter(cfg, backend="exact", clock=clock)

    differing = {c: 0 for c in COLUMNS}
    decisions = columns = 0
    pending: list = []
    seen: set = set()

    def settle():
        nonlocal decisions, columns
        for ticket, want in pending:
            got = dense.resolve(ticket)
            for c in COLUMNS:
                columns += 1
                if not np.array_equal(getattr(got, c), getattr(want, c)):
                    differing[c] += 1
            decisions += len(got)
        pending.clear()

    t_run = time.monotonic()
    for ids, gap in frames_of(args.seed, size["keys"], args.frames):
        now = clock.now()
        pending.append((dense.launch_ids(ids, now=now),
                        exact.allow_batch([str(int(i)) for i in ids],
                                          now=now)))
        seen.update(int(i) for i in ids)
        if len(pending) == INFLIGHT:
            settle()
        clock.advance(gap)
    settle()
    check_s = time.monotonic() - t_run

    stats = dense.directory_stats()
    table = dense._dir_keys().ravel()
    live = table[(table != directory.EMPTY) & (table != directory.TOMB)]
    want_keys = np.asarray(directory.join(*directory.canon(
        splitmix64(np.fromiter(seen, np.uint64, len(seen))))))
    held_once = (live.shape[0] == np.unique(live).shape[0]
                 and np.array_equal(np.sort(live), np.sort(want_keys)))
    fetches = dense.result_fetches
    dispatches = args.frames + 4

    timed = None
    if args.timed:
        more = frames_of(args.seed + 1, size["keys"], args.timed)
        more = [ids for ids, _ in more if ids.shape[0] == FRAME]
        if args.profile:
            jax.profiler.start_trace(args.profile)
        t0 = time.monotonic()
        tickets = []
        for ids in more:
            tickets.append(dense.launch_ids(ids))
            if len(tickets) > INFLIGHT:
                dense.resolve(tickets.pop(0))
            clock.advance(0.002)
        for ticket in tickets:
            dense.resolve(ticket)
        wall = time.monotonic() - t0
        if args.profile:
            jax.profiler.stop_trace()
        timed = {"frames": len(more), "wall_s": wall,
                 "ms_per_frame_host_clock": 1e3 * wall / len(more),
                 "stats_after": dense.directory_stats()}

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    ok = (not any(differing.values()) and stats["unplaced"] == 0
          and held_once and fetches == dispatches
          and stats["entries"] == len(seen) == stats["inserts"])
    out = {
        "ok": ok, "device": {"platform": dev.platform,
                             "kind": dev.device_kind},
        "size": size, "decisions": decisions, "columns": columns,
        "columns_differing": differing, "distinct_ids": len(seen),
        "directory": stats, "held_once": bool(held_once),
        "probes_per_lookup": stats["probes"] / max(1, stats["lookups"]),
        "fetches": fetches, "dispatches": dispatches,
        "check_s": check_s, "timed": timed, "memory_peak_bytes": peak}
    dense.close()
    exact.close()
    if args.profile:
        pbs = [os.path.join(d, f) for d, _, fs in os.walk(args.profile)
               for f in fs if f.endswith(".xplane.pb")]
        if pbs:
            dst = os.path.join(args.profile, "reduced.json")
            done = subprocess.run(
                [sys.executable, "-m", "chipbench.trace_reduce", pbs[0],
                 dst], cwd=ROOT, capture_output=True, text=True,
                env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
            if done.returncode == 0:
                with open(dst) as fh:
                    red = json.load(fh)
                out["trace"] = {k: red[k] for k in (
                    "step", "modules", "busy_s", "window_s", "device_ops")
                    if k in red}
            else:
                out["trace"] = {"error": done.stderr[-600:]}
    print(json.dumps(out))
    if not ok:
        return 1
    if rehearsal:
        print("rehearsal on cpu: passed; no accelerator, so no result")
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
