"""The collective router as a deployment (``mesh4-c3-coll``, ISSUE 27).

tests/test_collective_router.py pins the router against the host-routed
limiter. This file holds it to what a deployment is held to, on a forced
4-device CPU mesh at a small geometry, seeded:

* against the PLAIN rule — ``chipbench/reference.py``'s ``SlidingWindow``
  (a dict per key, integers, explicit ``now``; imports nothing from the
  program), through tools/collective_check.py's comparison, the one the
  chip run makes at the published widths: Zipf frames with in-frame
  duplicates across a sub-window and a window boundary, zero
  over-admission, and — at a width where no two keys can collide and at
  instants where the sub-window leaving the window holds nothing —
  replies equal outright;
* a frame forced into bin overflow is decided once, by the host router,
  and shows on ``/metrics`` as
  ``rate_limiter_collective_fallbacks_total{reason="overflow"}``;
* the launch is on ``tracing.span``: ``assemble``, ``place``, ``step``,
  ``writeback`` (and ``route``, ``prep``, ``finish``, ``barrier`` with
  the resolve's ``fetch`` inside it) under the flight recorder.

Each is parametrised over the hashed and the premix lane.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

import jax

from chipbench import promtext
from ratelimiter_tpu.observability import metrics as obs_metrics
from ratelimiter_tpu.observability import tracing
from ratelimiter_tpu.observability.decorators import MetricsDecorator
from ratelimiter_tpu.ops.hashing import splitmix64

_TOOL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "collective_check.py")
_spec = importlib.util.spec_from_file_location("collective_check", _TOOL)
check = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="the collective deployment needs >= 4 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

LANES = ("hashed", "premix")
#: 48 keys over 4 slices of d=4 x w=4,096: a key is over-counted only if
#: it shares a cell with another key in ALL four rows (~1e-9 a pair).
GEO = dict(depth=4, width=4096, sub_windows=6, keys=48)
LIMIT, WINDOW_S, SUB_S = 10, 60, 10.0
SIZES = (8, 203, 512)


@pytest.fixture
def pair():
    coll, host = check.make_pair(GEO, limit=LIMIT, window_s=WINDOW_S)
    try:
        yield coll, host
    finally:
        coll.close()
        host.close()


@pytest.fixture
def recorder():
    tracing.disable()
    rec = tracing.enable(1024)
    try:
        yield rec
    finally:
        tracing.disable()


def _run(pair, lane: str, times) -> "check.Tally":
    coll, host = pair
    tally = check.Tally(LIMIT, WINDOW_S, GEO["sub_windows"])
    # Zipf(0.6): the hottest of 48 keys is 7 % of a frame, so no bin
    # overflows and every frame takes the collective path.
    frames = check.zipf_frames(27, GEO["keys"], times, lanes=(lane,),
                               sizes=SIZES, s=0.6)
    check.run_frames(coll, host, frames, tally)
    assert tally.frames == len(times) * len(SIZES)
    assert tally.decisions == len(times) * sum(SIZES)
    assert coll.fallbacks == 0 and coll.dispatches == tally.frames
    return tally


@pytest.mark.parametrize("lane", LANES)
def test_replies_equal_the_plain_rule_outright(pair, lane):
    """Two instants in one sub-window, the next sub-window, then past the
    window: at none of them does the sub-window that is leaving the
    window hold anything, so the limiter's sliding estimate IS the plain
    ring's count and every reply is the rule's."""
    times = (0.0, 3.0, SUB_S + 1.0, WINDOW_S + 2 * SUB_S + 1.0,
             WINDOW_S + 2 * SUB_S + 5.0)
    tally = _run(pair, lane, times)
    assert tally.over_admitted == 0
    assert tally.ref_allowed_denials == 0
    assert tally.remaining_lower == tally.remaining_higher == 0
    assert tally.remaining_equal == tally.allowed > 0
    assert 0 < tally.allowed < tally.decisions      # the limit was reached
    assert tally.columns_differ == 0


@pytest.mark.parametrize("lane", LANES)
def test_never_laxer_than_the_rule_at_the_window_boundary(pair, lane):
    """Shortly before the first sub-window has left the window whole, the
    limiter still counts the share of it that is inside: stricter than
    the plain ring, never laxer. (A denial after that instant can be the
    residue of the weight: the conservative write rounds the fractional
    estimate up into the current sub-window's cell, where it stays.)"""
    times = (0.0, 3.0, SUB_S + 1.0, WINDOW_S + 0.85 * SUB_S,
             WINDOW_S + 1.5 * SUB_S)
    tally = _run(pair, lane, times)
    assert tally.over_admitted == 0
    assert tally.remaining_higher == 0
    assert tally.by_sliding_estimate > 0
    assert tally.ref_allowed_denials == (
        tally.by_sliding_estimate + tally.by_history + tally.by_collision)
    assert tally.remaining_lower > 0
    assert tally.columns_differ == 0


@pytest.mark.parametrize("lane", LANES)
def test_an_overflowing_frame_is_decided_once_and_counted(pair, lane):
    coll, host = pair
    registry = obs_metrics.Registry()
    decorated = MetricsDecorator(coll, registry=registry)
    tally = check.Tally(LIMIT, WINDOW_S, GEO["sub_windows"])
    # Every id of the frame is owned by slice 0: each source's bin for it
    # holds 2 x 128 / 4 = 64 of its 128 rows. Twice: see `remaining`.
    ids = check.overflow_ids(512, check.DEVICES)
    assert set((splitmix64(ids) % np.uint64(check.DEVICES)).tolist()) == {0}
    frames = [(lane, ids[:8], check.T0),            # fits: no fallback
              (lane, ids, check.T0 + 1.0), (lane, ids, check.T0 + 2.0)]
    check.run_frames(coll, host, frames, tally)
    assert tally.over_admitted == 0 and tally.ref_allowed_denials == 0
    # Distinct ids, one each a frame: all allowed, and the second frame's
    # `remaining` is the rule's, which it would not be had the step
    # written its state before the host router decided the frame again.
    assert tally.remaining_equal == tally.allowed == 8 + 512 + 512
    assert tally.columns_differ == 0
    assert coll.router_stats() == {
        "mode": "collective", "dispatches": 3, "placements": 12,
        "fallbacks": 2,
        "fallback_reasons": {"overflow": 2, "strict": 0}}
    scraped = {key: value
               for key, value in promtext.parse(registry.render()).items()
               if key[0].startswith("rate_limiter_collective_")}
    shard = ("shard", "0")
    assert scraped == {
        ("rate_limiter_collective_dispatches_total", (shard,)): 3.0,
        ("rate_limiter_collective_placements_total", (shard,)): 12.0,
        ("rate_limiter_collective_fallbacks_total",
         (("reason", "overflow"), shard)): 2.0,
        ("rate_limiter_collective_fallbacks_total",
         (("reason", "strict"), shard)): 0.0}
    decorated.close()
    assert not registry._collect_hooks


@pytest.mark.parametrize("lane", LANES)
def test_the_launch_is_on_the_span_primitive(pair, recorder, lane):
    coll, _ = pair
    ids = check.zipf_ids(np.random.default_rng(3), 203, GEO["keys"])
    if lane == "hashed":
        ticket = coll.launch_hashed(splitmix64(ids), now=check.T0)
    else:
        ticket = coll.launch_ids(ids, now=check.T0)
    launched = recorder.dump()              # by start: the outer span first
    assert [s["stage"] for s in launched] == [
        "route", "prep", "place", "assemble", "step", "writeback", "finish"]
    assert all(s["batch"] == 203 and s["outcome"] == tracing.OK
               for s in launched)
    by = {s["stage"]: s for s in launched}
    # Back to back inside "route": one clock read per boundary.
    order = ["prep", "place", "assemble", "step", "writeback", "finish"]
    for a, b in zip(order, order[1:]):
        assert by[a]["t_end_ns"] == by[b]["t_start_ns"]
    assert by["route"]["t_start_ns"] <= by["prep"]["t_start_ns"]
    assert by["finish"]["t_end_ns"] <= by["route"]["t_end_ns"]
    coll.resolve(ticket)
    # Resolve: the barrier, and inside it (after the wait) the one fetch
    # and, from the instant np.asarray returned, the NumPy rebuild.
    barrier, fetch, unpack = recorder.dump()[-3:]
    assert (barrier["stage"], fetch["stage"], unpack["stage"]) \
        == ("barrier", "fetch", "unpack")
    assert barrier["t_start_ns"] <= fetch["t_start_ns"]
    assert fetch["t_end_ns"] == unpack["t_start_ns"]
    assert unpack["t_end_ns"] <= barrier["t_end_ns"]
    assert fetch["batch"] == unpack["batch"] == 203


def test_spans_cost_nothing_with_tracing_off(pair):
    coll, _ = pair
    annotating = tracing.ANNOTATE     # an earlier test file may have left it on
    tracing.disable()
    tracing.annotate(False)
    try:
        assert tracing.span("assemble") is tracing.NO_SPAN
        res = coll.allow_hashed(np.arange(1, 9, dtype=np.uint64),
                                now=check.T0)
        assert res.allowed.all() and coll.dispatches == 1
    finally:
        tracing.annotate(annotating)


def test_the_strict_gate_counts_its_fallbacks():
    from ratelimiter_tpu import (Algorithm, Config, ManualClock,
                                 SketchParams, create_limiter)
    from ratelimiter_tpu.core.config import MeshSpec

    cfg = Config(
        algorithm=Algorithm.TPU_SKETCH, limit=LIMIT, window=float(WINDOW_S),
        sketch=SketchParams(depth=4, width=4096, sub_windows=6,
                            overload_policy="strict"),
        mesh=MeshSpec(devices=4, router="collective"))
    coll = create_limiter(cfg, backend="mesh", clock=ManualClock(check.T0))
    try:
        res = coll.allow_hashed(np.arange(1, 9, dtype=np.uint64),
                                now=check.T0)
        assert res.allowed.all()
        assert coll.router_stats() == {
            "mode": "collective", "dispatches": 0, "placements": 0,
            "fallbacks": 1,
            "fallback_reasons": {"overflow": 0, "strict": 1}}
    finally:
        coll.close()
