#!/usr/bin/env python3
"""The throughput deployment's coalesced dispatches against the plain
rule, at the published widths, at the library level with an explicit
clock (ISSUE 35, item 3).

    chiprun -- python3 tools/bulk_check.py            # the chip
    JAX_PLATFORMS=cpu python3 tools/bulk_check.py     # rehearsal

On the chip it runs at the published widths of ``cms-wide-b64k`` (d=3,
w=2**20, 60 sub-windows, limit 100 / 60 s, 262,144 keys, 4,096-id frames,
``--max-batch 65536``); with ``JAX_PLATFORMS=cpu`` it is a rehearsal at
w=65,536 with 16,384 keys, 256-id frames and a dispatch of 4,096 (counts
only, exit 3; ``--published`` rehearses the published widths). One
process, outside any timed window.

Seeded Zipf(1.1) frames are assembled into runs as the native door's
coalescer assembles them (native/server.cpp, ``dispatcher_main``): whole
frames in arrival order up to ``max_batch`` rows, a frame that does not
fit carved at the boundary, its rest opening the next run. At each of
five instants that cross a sub-window and a window boundary, on the
hashed and on the premix lane:

* sixteen whole frames, ``max_batch`` rows — the dispatch the cell makes;
* one run of ``2 x max_batch`` rows, the pad shape past (a lone frame
  larger than ``max_batch`` dispatches alone and pads there);
* a misaligned pair — an odd-sized frame, fifteen whole frames and the
  carved head of a sixteenth fill one run; the frame's rest and fifteen
  more, short of ``max_batch``, make the next (a padded dispatch).

The eight runs of an instant are launched back to back
(``launch_hashed`` / ``launch_ids``, eight tickets in flight, the
server's default ``--inflight``) and then resolved in order. The same
requests in the same order go through two copies of
``chipbench/reference.py``'s ``SlidingWindow`` exactly as
``tools/collective_check.py`` does (its ``Tally`` and
``against_reference`` are used, not copied): one decides for itself, one
is told what the limiter admitted.

Bounds: **0 over-admissions** on the limiter's own history; the
reference-allowed denials put down to a count-min collision at most 1 %
of decisions, the configuration's stated bound (those the limiter's own
history or the boundary sub-window's weight explains are counted beside
them); one fetch a dispatch; no dispatch answered by policy.

The last stdout line is one JSON object with the counts; exit 0 when
every bound holds, 1 when one does not, 3 for a passed rehearsal.

The column check of two checkouts on these same runs (one process a side:
a chip belongs to one process at a time):

    python3 tools/bulk_check.py --repo PARENT --dump a.npz
    python3 tools/bulk_check.py --dump b.npz
    python3 tools/bulk_check.py --compare a.npz b.npz

``--repo`` imports ``ratelimiter_tpu`` from another checkout (this file
and the reference stay this checkout's); ``--dump`` writes every
dispatch's four result columns and, after every instant, a digest of
every state leaf; ``--compare`` counts the columns and digests that
differ and exits 0 when none does.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_spec = importlib.util.spec_from_file_location(
    "collective_check", os.path.join(ROOT, "tools", "collective_check.py"))
cc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cc)

T0, LIMIT, WINDOW_S = cc.T0, cc.LIMIT, cc.WINDOW_S
INFLIGHT = 8
#: Published widths of cms-wide-b64k with its traffic's frame, and the
#: rehearsal's.
CHIP = dict(depth=3, width=1 << 20, sub_windows=60, keys=1 << 18,
            frame=4096, max_batch=1 << 16)
SMALL = dict(depth=3, width=1 << 16, sub_windows=60, keys=1 << 14,
             frame=256, max_batch=1 << 12)


def coalesce(frames: list, max_batch: int) -> tuple:
    """``frames`` (arrays of ids, arrival order) cut into runs as the
    door's dispatcher cuts its queue: whole frames while they fit, the
    frame that does not carved at the boundary (head into this run, the
    rest opening the next). Returns (runs, frames carved)."""
    runs, run, room, carved = [], [], max_batch, 0
    for ids in frames:
        while ids.size > room:
            carved += 1
            run.append(ids[:room])
            ids = ids[room:]
            runs.append(np.concatenate(run))
            run, room = [], max_batch
        run.append(ids)
        room -= ids.size
        if room == 0:
            runs.append(np.concatenate(run))
            run, room = [], max_batch
    if run:
        runs.append(np.concatenate(run))
    return runs, carved


def runs_of_an_instant(rng, geo: dict) -> tuple:
    """The four runs one lane sees at one instant (module docstring),
    and how many frames were carved making them."""
    frame, top, keys = geo["frame"], geo["max_batch"], geo["keys"]
    per = top // frame

    def draw(size):
        return cc.zipf_ids(rng, size, keys)

    full, none = coalesce([draw(frame) for _ in range(per)], top)
    past = [draw(2 * top)]                 # a lone oversized frame
    # An odd-sized frame first (the probe's, a control reply's) leaves
    # room for fifteen frames and a part of the sixteenth: a full run
    # that ends in a carved head, then the frame's rest and fifteen more,
    # short of max_batch.
    ragged, carved = coalesce(
        [draw(frame // 4 + 1)] + [draw(frame) for _ in range(2 * per - 1)],
        top)
    assert [r.size for r in full] == [top] and none == 0
    assert len(ragged) == 2 and ragged[0].size == top > ragged[1].size
    return full + past + ragged, carved


def make_limiter(geo: dict):
    from ratelimiter_tpu import (Algorithm, Config, ManualClock,
                                 SketchParams, create_limiter)

    cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=LIMIT,
                 window=float(WINDOW_S),
                 sketch=SketchParams(depth=geo["depth"], width=geo["width"],
                                     sub_windows=geo["sub_windows"]))
    return create_limiter(cfg, backend="sketch", clock=ManualClock(T0))


def run_instant(lim, runs: list, now: float, tally, dump=None) -> int:
    """Launch every (lane, ids) of ``runs`` at ``now`` before resolving
    any — at most INFLIGHT tickets in flight — then resolve in order and
    hold each against the plain rule. Returns rows answered by policy.
    ``dump`` (a dict) is given every result column."""
    from ratelimiter_tpu.ops.hashing import splitmix64

    policy = 0
    for at in range(0, len(runs), INFLIGHT):
        group = runs[at:at + INFLIGHT]
        hashes = [splitmix64(ids) for _, ids in group]
        tickets = [lim.launch_hashed(h, now=now) if lane == "hashed"
                   else lim.launch_ids(ids, now=now)
                   for (lane, ids), h in zip(group, hashes)]
        for h, ticket in zip(hashes, tickets):
            res = lim.resolve(ticket)
            policy += int(res.fail_open) * h.size
            tally.frames += 1
            cc.against_reference(h, res, now, tally)
            if dump is not None:
                for name in cc.COLUMNS:
                    dump[f"col.{tally.frames:03d}.{name}"] = np.asarray(
                        getattr(res, name))
    return policy


def state_digests(lim, tag: str, dump: dict) -> None:
    with lim._lock:
        leaves = dict(lim._state)
    for leaf, v in sorted(leaves.items()):
        dump[f"leaf.{tag}.{leaf}"] = np.frombuffer(
            hashlib.sha256(np.asarray(v).tobytes()).digest(), np.uint8)


def compare(a_path: str, b_path: str) -> int:
    a, b = np.load(a_path), np.load(b_path)
    names = sorted(set(a.files) | set(b.files))
    bad = [k for k in names if k not in a.files or k not in b.files
           or not np.array_equal(a[k], b[k])]
    cols = [k for k in names if k.startswith("col.")]
    leaves = [k for k in names if k.startswith("leaf.")]
    print(json.dumps({
        "result_columns": len(cols),
        "result_columns_differing": sum(k.startswith("col.") for k in bad),
        "state_digests": len(leaves),
        "state_digests_differing": sum(k.startswith("leaf.") for k in bad),
        "decisions": int(sum(a[k].size for k in cols
                             if k.endswith(".allowed") and k in a.files)),
        "first_differing": bad[:5]}))
    return 1 if bad else 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--published", action="store_true",
                    help="rehearse at the published widths on the CPU")
    ap.add_argument("--repo", help="import ratelimiter_tpu from this "
                                   "checkout instead of this file's")
    ap.add_argument("--dump", metavar="NPZ",
                    help="write every result column and state digest")
    ap.add_argument("--compare", nargs=2, metavar="NPZ")
    args = ap.parse_args(argv)
    if args.compare:
        return compare(*args.compare)
    if args.repo:
        sys.path.insert(0, os.path.abspath(args.repo))
    dump = {} if args.dump else None
    import jax

    from ratelimiter_tpu.core import jaxcfg

    jaxcfg.configure()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    rehearsal = device["platform"] == "cpu"
    geo = SMALL if rehearsal and not args.published else CHIP
    rng = np.random.default_rng(20260928)
    times = cc.instants(WINDOW_S / geo["sub_windows"], WINDOW_S)
    tally = cc.Tally(sub_windows=geo["sub_windows"])
    lim = make_limiter(geo)
    t0 = time.monotonic()
    try:
        sizes, carved, policy = set(), 0, 0
        for dt in times:
            runs = []
            for lane in ("hashed", "premix"):
                lane_runs, n = runs_of_an_instant(rng, geo)
                carved += n
                runs += [(lane, ids) for ids in lane_runs]
            sizes |= {int(ids.size) for _, ids in runs}
            policy += run_instant(lim, runs, T0 + dt, tally, dump)
            if dump is not None:
                state_digests(lim, f"{dt:.3f}", dump)
        fetches = lim.result_fetches
        accesses = getattr(lim, "table_access_stats", None)
    finally:
        lim.close()
    out = tally.as_dict()
    out.pop("columns"), out.pop("columns_differ")      # no second router here
    dispatches = out.pop("frames")     # the tally's "frame" is a dispatch here
    collisions_pct = 100.0 * out["by_collision"] / out["decisions"]
    denials_pct = 100.0 * out["ref_allowed_denials"] / out["decisions"]
    ok = (out["over_admitted"] == 0 and collisions_pct <= 1.0
          and fetches == dispatches and policy == 0)
    if dump is not None:
        np.savez(args.dump, **dump)
    print(json.dumps({"ok": ok, "rehearsal": rehearsal, "device": device,
                      "table_accesses": accesses,
                      "geometry": geo, "run_rows": sorted(sizes),
                      "dispatches": dispatches, "fetches": fetches,
                      "tickets_in_flight": INFLIGHT,
                      "frames_carved": carved, "policy_rows": policy,
                      "ref_allowed_denials_pct": denials_pct,
                      "collisions_pct": collisions_pct,
                      "seconds": time.monotonic() - t0, **out}))
    if not ok:
        return 1
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
