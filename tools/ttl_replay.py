"""The expiring exact deployment held to the plain rule for EVERY key of a
long stream (ISSUE 44, 4b; PERF.md section 6, PR 44): start the cell's
server with its configuration's flags alone, send a seeded Zipf stream of
whole ALLOW_HASHED frames through ``chipbench/wire.py`` one after another
on one connection (so the server decides them in the order they were
sent), keep each frame's send and reply instants, stop the server, and
then hold every reply of every key to ``chipbench/reference.py``'s token
bucket — a dict that never forgets: ``advance`` to the frame's two
instants, ``bounds`` for the row, ``apply`` the served answer. A key the
reclaim pass gave up and that came back must still answer inside the
interval of a bucket nobody forgot.

    chiprun -- python3 tools/ttl_replay.py [--cell exact-hashed-ttl]
        [--seed N] [--frames 4000] [--frame-keys 16384]

``--frame-keys`` replaces the traffic's 4,096 ids a frame: 16,384 is the
most rows one drain of the default native door takes (ISSUE 48), so each
frame is one dispatch of the largest shape the cell's server runs. Keep
it at or under the server's drain cap (256 in the CPU rehearsal): a
frame the door carves is decided at several instants, and the reference
here holds a frame's rows to one.

Prints one JSON line of counts: frames, decisions, seconds, replies
outside the reference's interval (``outside``, with the first few), the
server's own books over the stream — passes, entries given up, inserts,
``reinserted`` = inserts less the stream's distinct keys (each a key
given up and seen again), rows left unplaced, decisions answered by
policy — and ``idle_returns``, the client's own count of keys seen again
more than two windows after their last reply. Exit 0 when nothing is
outside, nothing unplaced, nothing answered by policy and at least one
pass ran; 1 otherwise. The parent never imports JAX (the server child
holds the chip); with ``JAX_PLATFORMS=cpu`` it is a rehearsal at the
cell's tiny geometry, frames of 256 ids, exit 3.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import struct
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import promtext, reference, runner      # noqa: E402
from chipbench.wire import T_ALLOW_HASHED, T_RESULT_HASHED, Wire  # noqa: E402

#: Apart from the load's ranks (from 0), the probe's and the cold keys'.
ID_BASE = 1 << 42
D = "rate_limiter_directory_"


def zipf_frames(population: int, s: float, frames: int, frame_keys: int,
                seed: int) -> np.ndarray:
    """``uint64[frames, frame_keys]``: ranks drawn Zipf(s) by the inverse
    of the distribution's running sum, rank -> id by an offset (the door
    finalizes ids with splitmix64, so neighbours do not share buckets)."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(1.0 / np.arange(1, population + 1, dtype=np.float64) ** s)
    ranks = np.searchsorted(cdf, rng.random(frames * frame_keys) * cdf[-1])
    return (ranks.astype(np.uint64) + np.uint64(ID_BASE)).reshape(
        frames, frame_keys)


def send(wire: Wire, ids: np.ndarray):
    """One ALLOW_HASHED frame through the wire client's own framing and
    checks; the body packed and the reply parsed by NumPy (4,096 ids a
    frame). Returns ``(allowed bool[n], remaining int64[n], policy)``."""
    n = ids.shape[0]
    body = (struct.pack("<I", n) + ids.astype("<u8").tobytes()
            + np.ones(n, "<u4").tobytes())
    resp = wire._roundtrip(T_ALLOW_HASHED, body, T_RESULT_HASHED)
    flags, _limit, got = struct.unpack_from("<BqI", resp)
    if got != n:
        raise RuntimeError(f"{got} replies to {n} requests")
    nbits = (n + 7) // 8
    bits = np.frombuffer(resp, np.uint8, nbits, 13)
    allowed = np.unpackbits(bits, bitorder="little")[:n].astype(bool)
    remaining = np.frombuffer(resp, "<i8", n, 13 + nbits)
    return allowed, remaining, bool(flags & 2)


def hold_to_reference(cfg: dict, stream, replies, instants) -> tuple:
    """Every reply against the never-forgetting reference. Returns
    ``(rows outside the interval, the first few of them, idle returns)``."""
    ref = reference.make(cfg["algorithm"], cfg["limit"], cfg["window_s"])
    horizon_us = 2 * cfg["window_s"] * 1_000_000
    last_reply: dict = {}
    outside, first, idle_returns = 0, [], 0
    for f, (ids, (allowed, remaining), (t_send, t_reply)) in enumerate(
            zip(stream, replies, instants)):
        keys = ids.tolist()
        for key in set(keys):
            seen = last_reply.get(key)
            if seen is not None and t_send - seen > horizon_us:
                idle_returns += 1
            last_reply[key] = t_reply
            ref.advance(key, t_send, t_reply)
        for i, (key, a, r) in enumerate(zip(keys, allowed.tolist(),
                                            remaining.tolist())):
            (may_deny, may_allow), (least, most) = ref.bounds(key)
            ok = (may_allow and least <= r <= most) if a else may_deny
            if not ok:
                outside += 1
                if len(first) < 5:
                    first.append({"frame": f, "row": i, "key": key,
                                  "allowed": a, "remaining": r,
                                  "reference": [may_deny, may_allow, least,
                                                most]})
            ref.apply(key, a)
    return outside, first, idle_returns


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="exact-hashed-ttl")
    ap.add_argument("--seed", type=int, default=2147487101)
    ap.add_argument("--frames", type=int, default=4000)
    ap.add_argument("--frame-keys", type=int, default=0)
    args = ap.parse_args()
    cell = runner.load_cell(args.cell)
    cfg, traffic = cell["config"], cell["traffic"]
    out_dir = os.path.join(runner.HERE, "out", f"replay-{args.cell}-{args.seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    stream = zipf_frames(cfg["key_population"], traffic["zipf_s"],
                         args.frames,
                         args.frame_keys or traffic["frame_keys"], args.seed)
    replies, instants, policy_frames = [], [], 0
    with runner.serving(cell, out_dir, trace=False) as srv:
        with Wire(srv.port) as wire:
            before = promtext.parse(wire.metrics())
            t0 = time.monotonic()
            for ids in stream:
                t_send = time.monotonic_ns() // 1000
                allowed, remaining, policy = send(wire, ids)
                instants.append((t_send, time.monotonic_ns() // 1000))
                replies.append((allowed, remaining))
                policy_frames += policy
            seconds = time.monotonic() - t0
            after = promtext.parse(wire.metrics())
    outside, first, idle_returns = hold_to_reference(cfg, stream, replies,
                                                     instants)

    def moved(name: str) -> float:
        return promtext.delta(before, after, D + name)

    distinct = int(np.unique(stream).shape[0])
    row = {
        "cell": args.cell, "seed": args.seed, "device": srv.device,
        "frames": args.frames, "decisions": int(stream.size),
        "seconds": seconds, "distinct_keys": distinct,
        "outside": outside, "first_outside": first,
        "allowed_share": float(np.mean([a.mean() for a, _ in replies])),
        "passes": moved("reclaim_passes_total"),
        "reclaimed": moved("reclaimed_total"),
        "reclaim_seconds": moved("reclaim_seconds_total"),
        "inserts": moved("inserts_total"),
        "reinserted": moved("inserts_total") - distinct,
        "idle_returns": idle_returns,
        "unplaced": moved("unplaced_total"),
        "policy_answered": (promtext.policy_answered(after)
                            - promtext.policy_answered(before)),
        "policy_frames": policy_frames,
        "entries_at_end": promtext.total(after, D + "entries"),
        "capacity": promtext.total(after, D + "capacity"),
    }
    print(json.dumps(row), flush=True)
    held = (outside == 0 and row["unplaced"] == 0
            and row["policy_answered"] == 0 and policy_frames == 0
            and row["passes"] >= 1)
    if not held:
        return 1
    return 3 if runner.REHEARSAL else 0


if __name__ == "__main__":
    sys.exit(main())
