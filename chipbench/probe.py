"""The correctness checks a run makes through the served path itself.

``probe``      before the load, on the fresh server and on the cell's own
               lane: 8 keys outside the load's id range, each sent
               ``limit + 50`` times — one frame with in-frame duplicates,
               then frames that cross the limit in mid-frame — and one
               fresh key. Every reply is held to chipbench/reference.py.
``cold_keys``  after the load: never-seen keys, once each; the share
               denied is the sketch's false-deny rate on a full table.
"""

from __future__ import annotations

import random
import time

from chipbench import reference
from chipbench.wire import Wire

HOT_KEYS = 8
PROBE_ID_BASE = 1 << 40
COLD_ID_BASE = 1 << 41
COLD_KEYS = 4096


class CheckFailed(Exception):
    """A check of the run did not hold; the run is not ``correct``."""


def _now_us() -> int:
    return time.monotonic_ns() // 1000


def _send(wire: Wire, lane: str, prefix: str, ids: list):
    """The load's string keys are ``user:<id>``; these use other prefixes."""
    if lane == "hashed":
        return wire.allow_hashed(ids)
    return wire.allow_batch([f"{prefix}{i}" for i in ids])


def _schedule(limit: int) -> list:
    """Times each hot key appears in each frame: one big frame, then
    frames of 7, summing to limit + 50."""
    sends = limit + 50
    big = min(58, sends)
    left = sends - big
    return [big] + [7] * (left // 7) + ([left % 7] if left % 7 else [])


def probe(wire: Wire, cfg: dict, lane: str, seed: int) -> dict:
    """Raises CheckFailed at the first reply that differs from the
    reference; returns what was sent."""
    limit = cfg["limit"]
    ref = reference.make(cfg["algorithm"], limit, cfg["window_s"],
                         cfg.get("sub_windows", 60))
    bucket = isinstance(ref, reference.TokenBucket)
    rng = random.Random(seed)
    hot = [PROBE_ID_BASE + i for i in range(HOT_KEYS)]
    fresh = PROBE_ID_BASE + (1 << 30)
    frames = [[k for k in hot for _ in range(reps)]
              for reps in _schedule(limit)] + [[fresh]]
    sent = allowed_total = 0
    allowed_per_key = dict.fromkeys(hot + [fresh], 0)
    for ids in frames:
        rng.shuffle(ids)
        t_send = _now_us()
        got = _send(wire, lane, "probe:", ids)
        t_reply = _now_us()
        if any(got.policy):
            raise CheckFailed("probe: a reply carried the fail_open flag")
        if len(got.allowed) != len(ids):
            raise CheckFailed(f"probe: {len(got.allowed)} replies to "
                              f"{len(ids)} requests")
        if bucket:
            for key in set(ids):
                ref.advance(key, t_send, t_reply)
        for i, key in enumerate(ids):
            a, r = got.allowed[i], got.remaining[i]
            if bucket:
                (may_deny, may_allow), (least, most) = ref.bounds(key)
                ok = (may_allow and least <= r <= most) if a else may_deny
                want = (f"allowed with {least}..{most} left" if may_allow
                        else "denied") + (" or denied" if may_deny
                                          and may_allow else "")
                ref.apply(key, a)
            else:
                want = ref.allow(key, t_reply)
                ok = (a, r) == want if want[0] else a is False
            if not ok:
                raise CheckFailed(
                    f"probe: request {sent + i} (key {key}) answered "
                    f"allowed={a} remaining={r}; the reference says {want}")
            allowed_per_key[key] += a
        sent += len(ids)
        allowed_total += sum(got.allowed)
    return {"sent": sent, "allowed": allowed_total,
            "allowed_per_hot_key": sorted({allowed_per_key[k] for k in hot}),
            "fresh_allowed": allowed_per_key[fresh]}


def cold_keys(wire: Wire, lane: str) -> dict:
    """COLD_KEYS never-seen keys, once each, on the cell's lane, in
    frames no server's ``--max-batch`` is under."""
    frame = 256
    denied = policy = 0
    for at in range(0, COLD_KEYS, frame):
        ids = [COLD_ID_BASE + at + i for i in range(min(frame,
                                                        COLD_KEYS - at))]
        got = _send(wire, lane, "cold:", ids)
        denied += sum(1 for a in got.allowed if not a)
        policy += sum(got.policy)
    return {"sent": COLD_KEYS, "denied": denied, "policy": policy,
            "cold_false_deny_pct": 100.0 * denied / COLD_KEYS}
