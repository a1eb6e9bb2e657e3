#!/usr/bin/env python3
"""The collective mesh router against the plain rule and the host router,
at the library level with an explicit clock (ISSUE 27, item 6b).

    chiprun --chips 4 -- python3 tools/collective_check.py        # the chip
    JAX_PLATFORMS=cpu python3 tools/collective_check.py           # rehearsal

On four chips it runs at the published widths of ``mesh4-c3-coll`` (d=4,
w=65,536, 60 sub-windows a slice, limit 100 / 60 s, 65,536 keys); with
``JAX_PLATFORMS=cpu`` it is a rehearsal on four virtual devices at a
small width (counts only, exit 3; ``--published`` rehearses the published
widths). One process, outside any timed window.

Seeded Zipf(1.1) frames of 8, 1,003 and 4,096 ids with in-frame
duplicates, on the hashed and the premix lane, at five instants that
cross a sub-window and a window boundary, then one frame built to
overflow a bin, go through ``CollectiveMeshLimiter`` and, the same
requests in the same order, through

* ``chipbench/reference.py``'s ``SlidingWindow`` (a dict per key,
  integers, explicit ``now``; imports nothing from the program), twice.
  One copy decides for itself: a request the limiter denied and it
  allowed is a reference-allowed denial. The other is told only what the
  limiter admitted: an admission it would refuse is an over-admission —
  the rule broken on the limiter's own history, which no earlier
  disagreement can excuse (after a denial the rule would not have made,
  the limiter holds LESS than the first copy, and may rightly allow what
  that copy then denies; those are counted apart). A reference-allowed
  denial is put down to the limiter's own history when that is at the
  limit; else to its sliding estimate when the key has admitted mass in
  the sub-window that is leaving the window, which the limiter still
  weighs by the share of it inside (``_boundary_weight``; the
  conservative write rounds that fraction up, so the gap is not
  recomputed here) — the program's stated semantics, stricter than the
  plain ring, never laxer; and otherwise to a count-min collision (or
  the residue of such a rounded-up weight in a later sub-window);
* ``SlicedMeshLimiter`` (``--router host``) on the same devices: every
  result column must be equal, byte for byte.

Bounds: no over-admission; no column differing from the host router;
reference-allowed denials <= 1 % of decisions; the two overflow frames, and no other,
decided by the host router; one slice per device. Reported beside them:
every reference-allowed denial with its cause, and ``remaining`` against
the rule's wherever both allow (lower under the sliding estimate; HIGHER
is possible under conservative update, whose cells skip an increment
where a heavier colliding key already holds the row up, and lose it when
that key's mass leaves the window — the sketch step's property, shared
with every router, counted here so that it is seen).

The last stdout line is one JSON object with the counts; exit 0 when
every bound holds, 1 when one does not, 3 for a passed rehearsal.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference  # noqa: E402  (imports nothing of the program)

T0 = 1_700_000_000.0
COLUMNS = ("allowed", "remaining", "retry_after", "reset_at")
SIZES = (8, 1003, 4096)
#: Published widths of mesh4-c3-coll, and the rehearsal's.
CHIP = dict(depth=4, width=65536, sub_windows=60, keys=65536)
SMALL = dict(depth=4, width=4096, sub_windows=60, keys=512)
LIMIT, WINDOW_S, DEVICES, ZIPF_S = 100, 60, 4, 1.1


def instants(sub_s: float, window_s: float) -> tuple:
    """Five instants: two in one sub-window, the next sub-window, a tenth
    of a sub-window before the first one has left the window whole (the
    boundary weight is 0.1 there), and the sub-window after."""
    return (0.0, 0.4 * sub_s, sub_s, window_s + 0.9 * sub_s,
            window_s + 1.5 * sub_s)


def make_pair(geo: dict, *, limit=LIMIT, window_s=WINDOW_S,
              devices=DEVICES, headroom=2.0):
    """(collective, host-routed) limiters of one geometry on the same
    ``devices`` devices, each on its own manual clock."""
    from ratelimiter_tpu import (Algorithm, Config, ManualClock,
                                 SketchParams, create_limiter)
    from ratelimiter_tpu.core.config import MeshSpec

    def build(router: str):
        cfg = Config(
            algorithm=Algorithm.TPU_SKETCH, limit=limit,
            window=float(window_s),
            sketch=SketchParams(depth=geo["depth"], width=geo["width"],
                                sub_windows=geo["sub_windows"]),
            mesh=MeshSpec(devices=devices, router=router,
                          bin_headroom=headroom))
        return create_limiter(cfg, backend="mesh", clock=ManualClock(T0))

    return build("collective"), build("host")


def zipf_ids(rng, size: int, keys: int, s: float = ZIPF_S) -> np.ndarray:
    """``size`` raw u64 ids drawn Zipf(s) over ``keys`` ranks; rank -> id
    by a fixed odd multiplier (a bijection on u64), so hot ids repeat
    inside a frame and spread over the slices once finalized."""
    p = 1.0 / np.arange(1, keys + 1, dtype=np.float64) ** s
    ranks = rng.choice(keys, size=size, p=p / p.sum()).astype(np.uint64)
    with np.errstate(over="ignore"):
        return (ranks + np.uint64(1)) * np.uint64(0x9E3779B97F4A7C15)


def overflow_ids(size: int, n_slices: int) -> np.ndarray:
    """Distinct raw ids that ONE slice owns, every one of them: each
    source's bin for that slice overflows at any headroom below the
    slice count."""
    from ratelimiter_tpu.ops.hashing import splitmix64_inv

    owned = (np.arange(1, size + 1, dtype=np.uint64) << np.uint64(20)) \
        * np.uint64(n_slices)
    return splitmix64_inv(owned)


class Tally:
    """Counts of one comparison run, and the two copies of the plain rule
    they are made against (see the module docstring)."""

    def __init__(self, limit: int = LIMIT, window_s: int = WINDOW_S,
                 sub_windows: int = 60):
        #: Told what the LIMITER admitted: is each admission within the
        #: rule, given everything admitted so far?
        self._own = reference.SlidingWindow(limit, window_s, sub_windows)
        #: Decides for itself, the same requests in the same order: what
        #: would the rule have answered?
        self._pure = reference.SlidingWindow(limit, window_s, sub_windows)
        #: (key, sub-window) -> admitted mass of a sub-window ``_own`` has
        #: already dropped (its count() forgets a sub-window the instant
        #: it leaves the plain ring).
        self._left: dict = {}
        self.decisions = self.allowed = self.rule_allowed = 0
        self.over_admitted = 0            # beyond the rule on its own history
        self.ref_allowed_denials = 0      # denied, the rule's own run allows
        self.by_sliding_estimate = 0      # ... the boundary weight explains
        self.by_history = 0               # ... own history is at the limit
        self.by_collision = 0             # ... neither: a CMS collision/residue
        self.ref_denied_allowances = 0    # allowed, the rule's own run denies
        self.remaining_equal = self.remaining_lower = 0
        self.remaining_higher = 0         # under-counted (see the docstring)
        self.columns = self.columns_differ = 0
        self.frames = 0

    def as_dict(self) -> dict:
        return {k: v for k, v in vars(self).items() if k[0] != "_"}


def against_reference(hashes: np.ndarray, res, now: float,
                      tally: Tally) -> None:
    """One frame's verdicts, in frame order, against the plain rule.
    ``hashes`` are the finalized key hashes."""
    own, pure = tally._own, tally._pure
    now_us = int(round(now * reference.MICRO))
    cur = now_us // own.sub_us
    # The share of the leaving sub-window still inside the window
    # (ops/sketch_kernels._boundary_weight).
    frac = 1.0 - (now_us - cur * own.sub_us) / own.sub_us
    allowed = np.asarray(res.allowed)
    remaining = np.asarray(res.remaining)
    for key, ok, rem in zip(hashes.tolist(), allowed.tolist(),
                            remaining.tolist()):
        # Read before count(): count() drops the leaving sub-window.
        gone = (key, cur - own.sub_windows)
        leaving = own._keys.get(key, {}).get(gone[1])
        if leaving is None:
            leaving = tally._left.get(gone, 0)
        else:
            tally._left[gone] = leaving
        used = own.count(key, now_us)
        rule_ok, _ = pure.allow(key, now_us)
        tally.decisions += 1
        tally.rule_allowed += rule_ok
        if ok:
            tally.allowed += 1
            tally.ref_denied_allowances += not rule_ok
            if used + 1 > own.limit:
                tally.over_admitted += 1
                continue
            _, own_rem = own.allow(key, now_us)
            if rem == own_rem:
                tally.remaining_equal += 1
            elif rem < own_rem:
                tally.remaining_lower += 1
            else:
                tally.remaining_higher += 1
        elif rule_ok:
            tally.ref_allowed_denials += 1
            if used + 1 > own.limit:
                tally.by_history += 1
            elif frac * leaving > 0:
                tally.by_sliding_estimate += 1
            else:
                tally.by_collision += 1


def against_host(res_c, res_h, tally: Tally) -> None:
    for col in COLUMNS:
        tally.columns += 1
        if not np.array_equal(getattr(res_c, col), getattr(res_h, col)):
            tally.columns_differ += 1


def run_frames(coll, host, frames, tally: Tally) -> None:
    """``frames``: (lane, raw ids, now). The hashed lane takes finalized
    hashes (what the native door's io thread hands over), the premix
    lane raw ids; the key is the finalized hash on both."""
    from ratelimiter_tpu.ops.hashing import splitmix64

    for lane, ids, now in frames:
        hashes = splitmix64(ids)
        if lane == "hashed":
            res_c = coll.allow_hashed(hashes, now=now)
            res_h = host.allow_hashed(hashes, now=now)
        else:
            res_c = coll.allow_ids(ids, now=now)
            res_h = host.allow_ids(ids, now=now)
        tally.frames += 1
        against_host(res_c, res_h, tally)
        against_reference(hashes, res_c, now, tally)


def zipf_frames(seed: int, keys: int, times, lanes=("hashed", "premix"),
                sizes=SIZES, s: float = ZIPF_S) -> list:
    rng = np.random.default_rng(seed)
    return [(lane, zipf_ids(rng, size, keys, s), T0 + dt)
            for dt in times for lane in lanes for size in sizes]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--published", action="store_true",
                    help="rehearse at the published widths on the CPU")
    args = ap.parse_args(argv)
    import jax

    from ratelimiter_tpu.core import jaxcfg

    jaxcfg.configure()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    rehearsal = device["platform"] == "cpu"
    if len(dev) < DEVICES:
        print(json.dumps({"ok": False, "device": device,
                          "error": f"needs {DEVICES} devices"}))
        return 2
    geo = SMALL if rehearsal and not args.published else CHIP
    coll, host = make_pair(geo)
    try:
        slice_devices = [int(s._device.id) for s in coll.slices]
        tally = Tally(sub_windows=geo["sub_windows"])
        times = instants(WINDOW_S / geo["sub_windows"], WINDOW_S)
        run_frames(coll, host, zipf_frames(20260927, geo["keys"], times),
                   tally)
        before = coll.router_stats()
        run_frames(coll, host,
                   [("hashed", overflow_ids(4096, DEVICES), T0 + times[-1]),
                    ("premix", overflow_ids(4096, DEVICES), T0 + times[-1])],
                   tally)
        stats = coll.router_stats()
    finally:
        coll.close()
        host.close()
    out = tally.as_dict()
    out["overflow_frames_decided_by_host_router"] = (
        stats["fallback_reasons"]["overflow"]
        - before["fallback_reasons"]["overflow"])
    out["fallbacks_before_the_overflow_frames"] = before["fallbacks"]
    out["collective_dispatches"] = stats["dispatches"]
    collisions_pct = 100.0 * out["by_collision"] / out["decisions"]
    denials_pct = 100.0 * out["ref_allowed_denials"] / out["decisions"]
    ok = (out["over_admitted"] == 0 and out["columns_differ"] == 0
          and denials_pct <= 1.0
          and out["overflow_frames_decided_by_host_router"] == 2
          and out["fallbacks_before_the_overflow_frames"] == 0
          and sorted(slice_devices) == list(range(DEVICES)))
    print(json.dumps({"ok": ok, "rehearsal": rehearsal, "device": device,
                      "slice_devices": slice_devices, "geometry": geo,
                      "ref_allowed_denials_pct": denials_pct,
                      "collisions_pct": collisions_pct, **out}))
    if not ok:
        return 1
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
