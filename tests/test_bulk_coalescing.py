"""The throughput deployment (``--max-batch`` = 16 x the clients' frame):
the native door coalesces frames from many connections into one device
dispatch, counts what it coalesced and what it carved, answers every
frame as the plain rule would, and never compiles after prewarm.

The plain rule is ``chipbench/reference.py``'s ``SlidingWindow`` (a dict
per key, integers, explicit ``now``; it imports nothing from the
program). Each connection owns its keys, so a key's requests arrive in
the order its connection sent them whatever the coalescer interleaves.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from chipbench import promtext, reference
from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.observability import Registry
from ratelimiter_tpu.serving import Client
from ratelimiter_tpu.serving.native_server import (
    NativeRateLimitServer,
    native_server_available,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T0 = 1_700_000_000.0
LIMIT, WINDOW_S, SUB_WINDOWS = 20, 60, 6
CONNS = 8

needs_door = pytest.mark.skipif(
    not native_server_available(), reason="needs g++ for the native server")


def _limiter(width: int = 4096):
    # 64 keys in four rows of 4,096 cells: no key shares all its cells.
    cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=LIMIT,
                 window=float(WINDOW_S),
                 sketch=SketchParams(depth=4, width=width,
                                     sub_windows=SUB_WINDOWS))
    return create_limiter(cfg, backend="sketch", clock=ManualClock(T0))


def _frames(conn: int, count: int, size: int) -> list:
    """``count`` frames of ``size`` ids over connection ``conn``'s own
    eight keys: every key repeats inside a frame and across frames."""
    rng = np.random.default_rng(1000 + conn)
    keys = (np.arange(8, dtype=np.uint64) + np.uint64(1 + 8 * conn)) \
        * np.uint64(0x9E3779B97F4A7C15)
    return [keys[rng.integers(0, 8, size=size)] for _ in range(count)]


def _serve(frames_by_conn: list, scrapes: int = 0, **door) -> tuple:
    """Every connection sends its frames, one at a time, all connections
    at once; one more connection scrapes /metrics over the wire
    ``scrapes`` times meanwhile (a control item in the decision queue).
    Returns (replies by connection, the door's stage counters, the
    parsed /metrics scrape)."""
    reg = Registry()
    lim = _limiter()
    srv = NativeRateLimitServer(lim, "127.0.0.1", 0, registry=reg, **door)
    srv.start()
    replies = [None] * len(frames_by_conn)
    gate = threading.Barrier(len(frames_by_conn) + 1)

    def client(i):
        with Client(port=srv.port, timeout=120.0) as c:
            c.allow_hashed(np.zeros(1, dtype=np.uint64) + np.uint64(1 << 50))
            gate.wait()
            replies[i] = [c.allow_hashed(f) for f in frames_by_conn[i]]

    def scraper():
        with Client(port=srv.port, timeout=120.0) as c:
            gate.wait()
            for _ in range(scrapes):
                c.metrics()

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(frames_by_conn))]
        threads.append(threading.Thread(target=scraper))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180.0)
        assert all(r is not None for r in replies)
        with Client(port=srv.port) as c:
            samples = promtext.parse(c.metrics())
        stage = srv.stats()["stage_ns"]
    finally:
        srv.shutdown()
        lim.close()
    return replies, stage, samples


def _as_the_rule_answers(frames_by_conn: list, replies: list) -> None:
    rule = reference.SlidingWindow(LIMIT, WINDOW_S, SUB_WINDOWS)
    now_us = int(T0 * reference.MICRO)
    for frames, outs in zip(frames_by_conn, replies):
        for ids, out in zip(frames, outs):
            assert len(out) == ids.size          # ONE reply, request order
            want = [rule.allow(key, now_us) for key in ids.tolist()]
            assert out.allowed.tolist() == [ok for ok, _ in want]
            assert out.remaining.tolist() == [rem for _, rem in want]


@needs_door
def test_frames_of_eight_connections_share_a_dispatch():
    """max_batch = 16 x the frame: the coalescer waits for more frames
    (max_delay) and drains what eight connections queued into ONE
    dispatch; every frame's reply is the plain rule's, row for row."""
    frame = 64
    frames = [_frames(i, 4, frame) for i in range(CONNS)]
    replies, stage, samples = _serve(frames, max_batch=16 * frame,
                                     max_delay=50e-3, inflight=8)
    _as_the_rule_answers(frames, replies)
    sent = CONNS * 4
    assert stage["frames"] >= sent and stage["carved"] == 0
    assert stage["frames"] / stage["batches"] > 1.0
    sizes = "rate_limiter_server_batch_size"
    mean = promtext.total(samples, sizes + "_sum") \
        / promtext.total(samples, sizes + "_count")
    assert mean > frame
    assert promtext.total(samples, "rate_limiter_door_frames_total") \
        == stage["frames"]
    assert promtext.total(samples, "rate_limiter_door_dispatches_total") \
        == stage["batches"]
    assert promtext.total(samples,
                          "rate_limiter_door_carved_frames_total") == 0


@needs_door
def test_a_control_frame_in_the_queue_takes_no_room_and_carves_nothing():
    """Eight 128-id frames fill max_batch = 1,024 exactly. A /metrics
    scrape queued among them holds no decision row: counted as one, it
    left 1,023 rows, the eighth frame was carved, and so was one frame
    of every run after it."""
    frames = [_frames(i, 6, 128) for i in range(CONNS)]
    replies, stage, _ = _serve(frames, scrapes=12, max_batch=1024,
                               max_delay=50e-3, inflight=8)
    _as_the_rule_answers(frames, replies)
    assert stage["carved"] == 0
    assert stage["frames"] == CONNS * 6 + CONNS      # + the hello frames


@needs_door
def test_a_misaligned_run_is_carved_counted_and_answered_as_one_frame():
    """300-id frames under max_batch 1,024: the fourth frame of a run
    does not fit, its head fills the dispatch and its rest opens the
    next. The reply still goes out as ONE frame in request order, equal
    to the plain rule's, and the door counts the cut."""
    frames = [_frames(i, 2, 300) for i in range(CONNS)]
    replies, stage, samples = _serve(frames, max_batch=1024,
                                     max_delay=50e-3, inflight=8)
    _as_the_rule_answers(frames, replies)
    assert stage["carved"] >= 1
    # A carved frame is a Pending in each of the two dispatches it fed.
    assert stage["frames"] >= CONNS * 2 + stage["carved"]
    assert promtext.total(samples, "rate_limiter_door_carved_frames_total") \
        == stage["carved"]


@pytest.mark.parametrize("max_batch", [64, 1024])
def test_no_compile_after_prewarm_for_any_run_length(max_batch):
    """_prewarm compiles every pad shape up to 2 x max_batch on both
    lanes; no run length the coalescer can cut (<= max_batch) nor the
    lone oversized frame's (<= 2 x max_batch) adds a program."""
    from ratelimiter_tpu.serving.__main__ import _prewarm

    # The compiled steps are shared by every limiter of one geometry in
    # the process (ops.memoized), so a count is taken as a difference:
    # what _prewarm ADDS is at most the pad shapes it owes (all of them on
    # a geometry nothing built before, as with this width when the file
    # runs alone), and what is THERE afterwards is at least those.
    lim = _limiter(width=2 * max_batch)
    try:
        steps = (lim._step, lim._get_ids_step())
        before = [s._cache_size() for s in steps]
        _prewarm(lim, max_batch)
        compiled = [s._cache_size() for s in steps]
        shapes = max_batch.bit_length() + 1 - 3       # 8 ... 2 x max_batch
        for had, has in zip(before, compiled):
            assert shapes <= has <= had + shapes, (before, compiled)
        edges = {1, 2 * max_batch}
        size = 8
        while size <= 2 * max_batch:
            edges |= {size - 1, size, min(size + 1, 2 * max_batch)}
            size *= 2
        for b in sorted(edges):
            ids = np.arange(b, dtype=np.uint64) + np.uint64(1 << 40)
            lim.resolve(lim.launch_hashed(ids, now=T0))
            lim.resolve(lim.launch_ids(ids, now=T0))
        assert [s._cache_size() for s in steps] == compiled
    finally:
        lim.close()


def test_the_bulk_check_rehearses_and_exits_3():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bulk_check.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 3, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["rehearsal"]
    assert out["over_admitted"] == 0 and out["fetches"] == out["dispatches"]
    assert out["frames_carved"] >= 1 and out["tickets_in_flight"] == 8
