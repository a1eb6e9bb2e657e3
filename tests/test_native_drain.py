"""The native door's coalescer has two numbers (ISSUE 48): ``max_batch``,
the queue depth that dispatches at once, and ``drain_cap``, the most
rows ONE drain takes of what is already queued. ``batch_rule`` decides
both: an explicit ``--max-batch N`` is (N, N) as before; left out, the
native door waits for 4,096 and drains up to 16,384 — whatever limiter
stands behind it — and a dispatch SLO or the asyncio door keep (4,096,
4,096).

The drains are made deterministic by plugging the dispatcher: the first
launch blocks inside the limiter until every frame of the case is
queued (``stats()["queued_keys"]``), so what each later launch took is a
function of the queue alone.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.core.config import DenseParams
from ratelimiter_tpu.serving import Client
from ratelimiter_tpu.serving.native_server import (
    AUTO_DRAIN_ROWS,
    AUTO_WAIT_ROWS,
    NativeRateLimitServer,
    batch_rule,
    native_server_available,
)

T0 = 1_700_000_000.0
FRAME = 4096

needs_door = pytest.mark.skipif(
    not native_server_available(), reason="needs g++ for the native server")


def _sketch(width: int = 8192):
    cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=1 << 20, window=60.0,
                 sketch=SketchParams(depth=2, width=width, sub_windows=6))
    return create_limiter(cfg, backend="sketch", clock=ManualClock(T0))


def _dense():
    cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=100, window=1.0,
                 dense=DenseParams(capacity=4096))
    return create_limiter(cfg, backend="dense", clock=ManualClock(T0))


# ------------------------------------------------------------- the rule


@pytest.mark.parametrize("given, native, slo, want", [
    (None, True, False, (AUTO_WAIT_ROWS, AUTO_DRAIN_ROWS)),
    (None, True, True, (AUTO_WAIT_ROWS, AUTO_WAIT_ROWS)),
    (None, False, False, (AUTO_WAIT_ROWS, AUTO_WAIT_ROWS)),
    (None, False, True, (AUTO_WAIT_ROWS, AUTO_WAIT_ROWS)),
    (256, True, False, (256, 256)),
    (256, True, True, (256, 256)),
    (256, False, False, (256, 256)),
    (4096, True, False, (4096, 4096)),
    (65536, True, False, (65536, 65536)),
    (65536, False, True, (65536, 65536)),
])
def test_the_rule(given, native, slo, want):
    assert batch_rule(given, native=native, slo=slo) == want


def test_the_defaults_are_a_frame_and_four():
    assert (AUTO_WAIT_ROWS, AUTO_DRAIN_ROWS) == (4096, 16384)


@needs_door
@pytest.mark.parametrize("make", [_sketch, _dense], ids=["sketch", "dense"])
@pytest.mark.parametrize("door, want", [
    ({}, (4096, 16384)),
    ({"max_batch": 4096}, (4096, 4096)),
    ({"max_batch": 256}, (256, 256)),
    ({"dispatch_timeout": 0.5}, (4096, 4096)),
], ids=["default", "explicit-4096", "explicit-256", "slo"])
def test_a_door_takes_the_rule_whatever_limiter_it_fronts(make, door, want):
    """No exemption by the limiter's type: the dense backend's door drains
    what is queued like any other."""
    lim = make()
    srv = NativeRateLimitServer(lim, "127.0.0.1", 0, **door)
    try:
        assert (srv.max_batch, srv.drain_cap) == want
    finally:
        srv.shutdown()
        lim.close()


def test_the_flag_is_not_given_by_default():
    from ratelimiter_tpu.serving.__main__ import build_parser

    assert build_parser().parse_args([]).max_batch is None
    assert build_parser().parse_args(["--max-batch", "256"]).max_batch == 256


# ------------------------------------------------------------ the drains


class _Plugged:
    """A limiter whose FIRST launch waits for ``release``; every launch's
    row count is kept."""

    def __init__(self, lim):
        self.lim, self.sizes = lim, []
        self.plugged, self.release = threading.Event(), threading.Event()
        inner = lim.launch_hashed

        def launch_hashed(h64, ns=None, *a, **kw):
            first = not self.plugged.is_set()
            if first:
                self.plugged.set()
                assert self.release.wait(60.0)
            else:
                self.sizes.append(int(np.asarray(h64).shape[0]))
            return inner(h64, ns, *a, **kw)

        lim.launch_hashed = launch_hashed


def _ids(conn: int, n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.uint64) + np.uint64(1 + (conn << 20))) \
        * np.uint64(0x9E3779B97F4A7C15)


def _drain(frames: list, strings: int = 0, **door) -> tuple:
    """Queue every frame (one connection each, ``strings`` more sending
    one 64-key string frame each) behind a plugged dispatcher, let go,
    and return (rows of each launch after the plug, replies, stats —
    with ``waited_s``, what the dispatcher then spent idle)."""
    lim = _sketch()
    plug = _Plugged(lim)
    srv = NativeRateLimitServer(lim, "127.0.0.1", 0, inflight=8, **door)
    srv.start()
    replies = [None] * (len(frames) + strings)

    def hashed(i):
        with Client(port=srv.port, timeout=120.0) as c:
            replies[i] = c.allow_hashed(frames[i])

    def stringly(i):
        with Client(port=srv.port, timeout=120.0) as c:
            replies[i] = c.allow_batch(
                [f"user:{i}:{j}" for j in range(64)])

    def opener():
        with Client(port=srv.port, timeout=120.0) as c:
            c.allow_hashed(_ids(999, 8))

    try:
        first = threading.Thread(target=opener)
        first.start()
        assert plug.plugged.wait(60.0)
        threads = [threading.Thread(target=hashed, args=(i,))
                   for i in range(len(frames))]
        threads += [threading.Thread(target=stringly, args=(len(frames) + j,))
                    for j in range(strings)]
        for t in threads:
            t.start()
        want = sum(f.shape[0] for f in frames) + 64 * strings
        deadline = time.monotonic() + 60.0
        while srv.stats()["queued_keys"] < want:
            assert time.monotonic() < deadline, srv.stats()["queued_keys"]
            time.sleep(0.005)
        # The plugged dispatcher is inside Python: its idle clock (the
        # coalescing wait's state) stands still until it is let go.
        idle0 = srv.stats()["thread_ns"]["dispatcher"]["idle"]
        plug.release.set()
        for t in threads:
            t.join(timeout=120.0)
        stats = srv.stats()
        stats["waited_s"] = (stats["thread_ns"]["dispatcher"]["idle"]
                             - idle0) / 1e9
        first.join(timeout=120.0)
        assert all(r is not None for r in replies)
    finally:
        plug.release.set()
        srv.shutdown()
        lim.close()
    return plug.sizes, replies, stats


@needs_door
@pytest.mark.parametrize("k", range(1, 10))
def test_k_queued_frames_leave_four_a_drain(k):
    """k frames of 4,096 ids queued: a drain takes min(what is left, 4);
    the fifth frame waits for the next one. Nothing is carved."""
    sizes, replies, stats = _drain([_ids(i, FRAME) for i in range(k)])
    want = [FRAME * min(4, k - at) for at in range(0, k, 4)]
    assert sizes == want
    assert all(len(r) == FRAME and r.allowed.all() for r in replies)
    assert stats["stage_ns"]["carved"] == 0
    assert stats["stage_ns"]["frames"] == k + 1          # + the plug's
    assert stats["stage_ns"]["batches"] == len(want) + 1


@needs_door
@pytest.mark.parametrize("n, want, carved", [
    (20000, [16384, 3616], 1),
    (10000, [10000], 0),
    (16384, [16384], 0),
    (16385, [16384, 1], 1),
], ids=["20000", "10000", "16384", "16385"])
def test_one_frame_carves_at_the_drain_cap_and_not_under_it(n, want, carved):
    sizes, replies, stats = _drain([_ids(0, n)])
    assert sizes == want
    assert len(replies[0]) == n and replies[0].allowed.all()
    assert stats["stage_ns"]["carved"] == carved


@needs_door
@pytest.mark.parametrize("max_batch, frames, want", [
    (256, [256] * 3, [256, 256, 256]),
    (256, [600], [256, 256, 88]),
    (4096, [4096] * 3, [4096, 4096, 4096]),
    (4096, [2048] * 4, [4096, 4096]),
    (65536, [4096] * 9, [36864]),
    (65536, [4096] * 17, [65536, 4096]),
], ids=["256x3", "256-carve", "4096x3", "4096-halves", "65536x9",
        "65536x17"])
def test_an_explicit_max_batch_is_both_numbers_as_before(max_batch, frames,
                                                         want):
    sizes, _, _ = _drain([_ids(i, n) for i, n in enumerate(frames)],
                         max_batch=max_batch)
    assert sizes == want


@needs_door
def test_strings_and_hashed_items_of_one_drain_split_into_their_groups():
    """Three hashed frames and two string frames queued: ONE drain takes
    them all (12,416 rows < 16,384) and dispatches the string rows and
    the hashed rows as a group each, in either order."""
    sizes, replies, stats = _drain([_ids(i, FRAME) for i in range(3)],
                                   strings=2)
    assert sorted(sizes) == [128, 3 * FRAME]
    assert [len(r) for r in replies] == [FRAME] * 3 + [64] * 2
    assert stats["stage_ns"]["carved"] == 0


@needs_door
@pytest.mark.parametrize("frames, waits", [
    ([4095], True), ([4096], False), ([4095, 4095], False),
], ids=["4095", "4096", "2x4095"])
def test_the_wait_threshold_stays_a_frame(frames, waits):
    """A dispatcher that comes back to a queue of 4,096 keys or more
    dispatches at once — 8,190 are under the drain cap and still do not
    wait — and a thinner queue waits its --max-delay out: the threshold
    is max_batch, not the drain cap."""
    sizes, _, stats = _drain([_ids(i, n) for i, n in enumerate(frames)],
                             max_delay=0.5)
    assert sizes == [sum(frames)]
    took = stats["waited_s"]
    assert (took >= 0.45) if waits else (took < 0.25), took


# ---------------------------------------------------------- the prewarm


def test_no_compile_after_the_default_doors_prewarm_at_any_pad():
    """Handed the drain cap, _prewarm compiles 8 ... 32,768 on both
    lanes; no run the default door can cut adds a program."""
    from ratelimiter_tpu.serving.__main__ import _prewarm

    lim = _sketch(width=1 << 16)         # a geometry of this test's own
    try:
        _, drain_rows = batch_rule(None)
        steps = (lim._step, lim._get_ids_step())
        before = [s._cache_size() for s in steps]
        _prewarm(lim, drain_rows)
        compiled = [s._cache_size() for s in steps]
        # 2^3 ... 2^15: thirteen shapes there, at most thirteen added
        # (the process memoises a geometry's steps: a difference, never
        # a total).
        for had, has in zip(before, compiled):
            assert 13 <= has <= had + 13, (before, compiled)
        for b in (1, 4096, 4097, 6550, 8192, 8193, 12288, 16384, 16385,
                  32768):
            ids = np.arange(b, dtype=np.uint64) + np.uint64(1 << 40)
            lim.resolve(lim.launch_hashed(ids, now=T0))
            lim.resolve(lim.launch_ids(ids, now=T0))
        assert [s._cache_size() for s in steps] == compiled
    finally:
        lim.close()
