"""Zero-copy hashed wire lane — protocol + both front doors (ADR-011).

T_ALLOW_HASHED carries raw u64 key ids columnar; the server parses them
as np.frombuffer views, stages them with one memcpy, hashes ON DEVICE,
and answers columnar T_RESULT_HASHED (packed by wire_pack at resolve on the
asyncio door). These tests pin the frame formats, the end-to-end
equivalence with the direct limiter lane, and the error surface on both
doors.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from ratelimiter_tpu import Algorithm, Config, SketchParams
from ratelimiter_tpu.algorithms.sketch import SketchLimiter
from ratelimiter_tpu.core.clock import ManualClock
from ratelimiter_tpu.core.errors import InvalidConfigError, InvalidNError
from ratelimiter_tpu.core.types import BatchResult
from ratelimiter_tpu.serving import protocol as p

T0 = 1_000_000.0


def _cfg(**kw) -> Config:
    return Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5, window=10.0,
                  sketch=SketchParams(depth=3, width=256, sub_windows=5),
                  **kw)


# ------------------------------------------------------------- protocol


def test_allow_hashed_roundtrip_is_columnar_and_zero_copy():
    ids = np.arange(10, 30, dtype=np.uint64)
    ns = np.arange(1, 21, dtype=np.uint32)
    frame = p.encode_allow_hashed(7, ids, ns)
    length, type_, req_id = p.parse_header(frame[:p.HEADER_SIZE])
    assert (type_, req_id) == (p.T_ALLOW_HASHED, 7)
    body = frame[p.HEADER_SIZE:]
    assert len(body) == length - 9
    got_ids, got_ns = p.parse_allow_hashed(body)
    np.testing.assert_array_equal(got_ids, ids)
    np.testing.assert_array_equal(got_ns, ns)
    # Zero copy: the views alias the body buffer, no materialization.
    assert got_ids.base is not None and not got_ids.flags.writeable


def test_parse_allow_hashed_rejects_malformed():
    with pytest.raises(p.ProtocolError):
        p.parse_allow_hashed(b"\x01")
    body = p._HASHED_HEAD.pack(3) + b"\x00" * 20  # 3 items need 36 B
    with pytest.raises(p.ProtocolError):
        p.parse_allow_hashed(body)


def test_result_hashed_views_are_zero_copy_and_frame_identical():
    """ISSUE-5 satellite (the named ADR-011 residual): the writev-style
    reply builder must frame the three value columns as MEMORYVIEWS
    straight over the resolve's wire_packed words buffer — buffer
    identity asserted via np.shares_memory — with no intermediate
    per-frame bytes join, and the concatenation of the views must be
    byte-identical to the single-buffer encoder."""
    lim = SketchLimiter(_cfg(), ManualClock(T0))
    ids = np.arange(1, 42, dtype=np.uint64)  # 41 ids: partial mask byte
    res = lim.resolve(lim.launch_ids(ids, wire=True))
    assert res.wire_packed is not None
    _bits, words, _padded = res.wire_packed

    views = p.encode_result_hashed_views(9, res)
    assert len(views) == 4
    # Zero extra copies: every column view aliases the resolve fetch.
    for v in views[1:]:
        assert isinstance(v, memoryview)
        assert np.shares_memory(np.frombuffer(v, dtype=np.uint8), words)
    # And the scatter-gather list is the SAME frame the bytes encoder
    # builds (parseable by the client untouched).
    joined = b"".join(bytes(v) for v in views)
    assert joined == p.encode_result_hashed(9, res)
    parsed = p.parse_result_hashed(joined[p.HEADER_SIZE:])
    np.testing.assert_array_equal(parsed.allowed, res.allowed)
    np.testing.assert_array_equal(parsed.remaining, res.remaining)
    lim.close()


def test_result_hashed_views_fall_back_without_packed_buffers():
    res = BatchResult(
        allowed=np.array([True, False, True]),
        limit=5,
        remaining=np.array([4, 0, 3], dtype=np.int64),
        retry_after=np.array([0.0, 1.5, 0.0]),
        reset_at=np.array([T0 + 10] * 3),
    )
    views = p.encode_result_hashed_views(3, res)
    assert len(views) == 1
    assert bytes(views[0]) == p.encode_result_hashed(3, res)


def test_result_hashed_roundtrip():
    res = BatchResult(
        allowed=np.array([True, False, True, True, False]),
        limit=42,
        remaining=np.array([4, 0, 1, 2, 0], np.int64),
        retry_after=np.array([0.0, 1.5, 0.0, 0.0, 2.25]),
        reset_at=np.full(5, 123.5),
        fail_open=True,
    )
    frame = p.encode_result_hashed(9, res)
    _, type_, req_id = p.parse_header(frame[:p.HEADER_SIZE])
    assert (type_, req_id) == (p.T_RESULT_HASHED, 9)
    back = p.parse_result_hashed(frame[p.HEADER_SIZE:])
    np.testing.assert_array_equal(back.allowed, res.allowed)
    np.testing.assert_array_equal(back.remaining, res.remaining)
    np.testing.assert_array_equal(back.retry_after, res.retry_after)
    np.testing.assert_array_equal(back.reset_at, res.reset_at)
    assert back.limit == 42 and back.fail_open


# ------------------------------------------------------- asyncio door


def _run(coro):
    return asyncio.run(coro)


def test_asyncio_door_hashed_lane_matches_direct():
    from ratelimiter_tpu.serving.client import AsyncClient
    from ratelimiter_tpu.serving.server import run_server

    async def main():
        lim = SketchLimiter(_cfg(), ManualClock(T0))
        oracle = SketchLimiter(_cfg(), ManualClock(T0))
        srv = await run_server(lim, port=0)
        c = await AsyncClient.connect(port=srv.port)
        rng = np.random.default_rng(2)
        try:
            for _ in range(4):
                ids = rng.integers(1, 30, size=50).astype(np.uint64)
                ns = rng.integers(1, 3, size=50).astype(np.uint32)
                got = await c.allow_hashed(ids, ns)
                want = oracle.allow_ids(ids, ns.astype(np.int64))
                np.testing.assert_array_equal(got.allowed, want.allowed)
                np.testing.assert_array_equal(got.remaining, want.remaining)
                np.testing.assert_array_equal(got.retry_after,
                                              want.retry_after)
                np.testing.assert_array_equal(got.reset_at, want.reset_at)
                assert got.limit == want.limit
        finally:
            await c.close()
            await srv.shutdown()
            lim.close()
            oracle.close()

    _run(main())


def test_asyncio_door_hashed_errors_and_empty():
    from ratelimiter_tpu.serving.client import AsyncClient
    from ratelimiter_tpu.serving.server import run_server

    async def main():
        lim = SketchLimiter(_cfg(), ManualClock(T0))
        srv = await run_server(lim, port=0)
        c = await AsyncClient.connect(port=srv.port)
        try:
            empty = await c.allow_hashed(np.zeros(0, np.uint64))
            assert len(empty) == 0
            with pytest.raises(InvalidNError):
                await c.allow_hashed(np.arange(3, dtype=np.uint64),
                                     np.zeros(3, np.uint32))
        finally:
            await c.close()
            await srv.shutdown()
            lim.close()

    _run(main())


def test_asyncio_door_hashed_rejects_non_sketch_backend():
    from ratelimiter_tpu.algorithms.exact import ExactLimiter
    from ratelimiter_tpu.serving.client import AsyncClient
    from ratelimiter_tpu.serving.server import run_server

    async def main():
        lim = ExactLimiter(Config(algorithm=Algorithm.FIXED_WINDOW,
                                  limit=5, window=10.0), ManualClock(T0))
        srv = await run_server(lim, port=0)
        c = await AsyncClient.connect(port=srv.port)
        try:
            with pytest.raises(InvalidConfigError):
                await c.allow_hashed(np.arange(3, dtype=np.uint64))
        finally:
            await c.close()
            await srv.shutdown()
            lim.close()

    _run(main())


def test_hashed_lane_interleaves_with_string_lane():
    """Hashed frames and string traffic share the batcher's pipeline:
    both lanes answer correctly on one connection, and per-key ordering
    within each lane holds."""
    from ratelimiter_tpu.serving.client import AsyncClient
    from ratelimiter_tpu.serving.server import run_server

    async def main():
        lim = SketchLimiter(_cfg(), ManualClock(T0))
        srv = await run_server(lim, port=0)
        c = await AsyncClient.connect(port=srv.port)
        try:
            ids = np.full(3, 99, dtype=np.uint64)
            r1, s1, r2 = await asyncio.gather(
                c.allow_hashed(ids),
                c.allow_n("stringkey", 1),
                c.allow_hashed(ids))
            # limit 5 on one id: 3 + at most 2 more allowed.
            assert int(r1.allowed.sum()) + int(r2.allowed.sum()) == 5
            assert s1.allowed
        finally:
            await c.close()
            await srv.shutdown()
            lim.close()

    _run(main())


# ------------------------------------------------ decorator interposition


def test_circuit_breaker_guards_hashed_lane():
    """The breaker must admit/judge hashed-lane dispatches exactly like
    string batches: hashed failures open it, and while OPEN the hashed
    lane is short-circuited (no device work enqueued) — the review gap
    that motivated the explicit decorator delegation (ADR-011)."""
    from ratelimiter_tpu.observability.decorators import (
        CircuitBreakerDecorator,
    )

    inner = SketchLimiter(_cfg(fail_open=True), ManualClock(T0))
    lim = CircuitBreakerDecorator(inner, failure_threshold=2,
                                  cooldown=60.0)
    try:
        ids = np.arange(1, 9, dtype=np.uint64)
        assert lim.allow_ids(ids).allowed.all()
        inner.inject_failure()
        # Failures through the HASHED lane must trip the breaker.
        for _ in range(2):
            out = lim.allow_ids(ids)
            assert out.fail_open
        assert lim.state == "open"
        inner.heal()
        # While open, hashed launches are short-circuited — no dispatch
        # reaches the backend (its counters must not move).
        before = inner.in_window_admitted_mass()
        t = lim.launch_ids(ids, wire=True)
        out = lim.resolve(t)
        assert out.fail_open
        assert inner.in_window_admitted_mass() == before
    finally:
        lim.close()


def test_metrics_decorator_observes_hashed_lane():
    from ratelimiter_tpu.observability.decorators import MetricsDecorator
    from ratelimiter_tpu.observability.metrics import Registry

    reg = Registry()
    inner = SketchLimiter(_cfg(), ManualClock(T0))
    lim = MetricsDecorator(inner, registry=reg)
    try:
        lim.allow_ids(np.arange(1, 9, dtype=np.uint64))
        text = reg.render()
        assert ('rate_limiter_decisions_allowed_total'
                '{algorithm="sliding_window"} 8') in text
    finally:
        lim.close()


# -------------------------------------------------------- native door


needs_native = pytest.mark.skipif(
    not __import__("ratelimiter_tpu.serving.native_server",
                   fromlist=["native_server_available"]
                   ).native_server_available(),
    reason="native server extension unavailable (no g++)")


@needs_native
@pytest.mark.parametrize("shards", [1, 3])
def test_native_door_hashed_lane_matches_direct(shards):
    from ratelimiter_tpu.ops.hashing import splitmix64
    from ratelimiter_tpu.serving.client import Client
    from ratelimiter_tpu.serving.native_server import NativeRateLimitServer

    lim = SketchLimiter(_cfg())
    srv = NativeRateLimitServer(lim, port=0, shards=shards, inflight=4)
    srv.start()
    c = Client(port=srv.port)
    oracles = [SketchLimiter(_cfg()) for _ in range(shards)]
    try:
        rng = np.random.default_rng(4)
        for _ in range(3):
            ids = rng.integers(1, 40, size=64).astype(np.uint64)
            got = c.allow_hashed(ids)
            assert len(got) == 64
            # Oracle: per-shard replay with the same routing (C++ routes
            # on the finalized hash; shard_of_id is the Python mirror).
            want_allowed = np.zeros(64, bool)
            by_shard = {}
            for i, raw in enumerate(ids.tolist()):
                by_shard.setdefault(srv.shard_of_id(raw), []).append(i)
            fin = splitmix64(ids)
            for sh, idxs in by_shard.items():
                out = oracles[sh].allow_hashed(fin[idxs])
                want_allowed[idxs] = out.allowed
            np.testing.assert_array_equal(got.allowed, want_allowed)
    finally:
        c.close()
        srv.shutdown()
        lim.close()
        for o in oracles:
            o.close()


@needs_native
def test_native_door_hashed_error_surface():
    from ratelimiter_tpu.algorithms.exact import ExactLimiter
    from ratelimiter_tpu.serving.client import Client
    from ratelimiter_tpu.serving.native_server import NativeRateLimitServer

    lim = SketchLimiter(_cfg())
    srv = NativeRateLimitServer(lim, port=0, inflight=4)
    srv.start()
    c = Client(port=srv.port)
    try:
        with pytest.raises(InvalidNError):
            c.allow_hashed(np.arange(3, dtype=np.uint64),
                           np.zeros(3, np.uint32))
        assert len(c.allow_hashed(np.zeros(0, np.uint64))) == 0
    finally:
        c.close()
        srv.shutdown()
        lim.close()

    # A non-sketch backend answers E_INVALID_CONFIG for hashed frames.
    elim = ExactLimiter(Config(algorithm=Algorithm.FIXED_WINDOW, limit=5,
                               window=10.0))
    esrv = NativeRateLimitServer(elim, port=0)
    esrv.start()
    ec = Client(port=esrv.port)
    try:
        with pytest.raises(InvalidConfigError):
            ec.allow_hashed(np.arange(3, dtype=np.uint64))
    finally:
        ec.close()
        esrv.shutdown()
        elim.close()
