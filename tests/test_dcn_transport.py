"""DCN exchange over a real transport: T_DCN_PUSH frames between
servers (VERDICT r3 item 5 — two OS processes exchanging history via the
serving protocol, converging within the documented staleness envelope)."""

from __future__ import annotations

import asyncio
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from netutil import free_port

from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.serving import Client, RateLimitServer
from ratelimiter_tpu.serving import protocol as p

T0 = 1_700_000_000.0


class TestDcnFrames:
    def test_slabs_roundtrip(self):
        periods = np.array([5, 9], dtype=np.int64)
        slabs = np.arange(2 * 3 * 16, dtype=np.int32).reshape(2, 3, 16)
        frame = p.encode_dcn_slabs(7, periods, slabs, 1_000_000)
        length, type_, rid = p.parse_header(frame[:p.HEADER_SIZE])
        assert type_ == p.T_DCN_PUSH and rid == 7
        kind, got_p, got_s = p.parse_dcn(frame[p.HEADER_SIZE:], 3, 16,
                                         1_000_000)
        assert kind == p.DCN_KIND_SLABS
        np.testing.assert_array_equal(got_p, periods)
        np.testing.assert_array_equal(got_s, slabs)

    def test_debt_roundtrip(self):
        delta = np.arange(3 * 16, dtype=np.int64).reshape(3, 16)
        frame = p.encode_dcn_debt(9, delta)
        kind, got, _ = p.parse_dcn(frame[p.HEADER_SIZE:], 3, 16, 0)
        assert kind == p.DCN_KIND_DEBT
        np.testing.assert_array_equal(got, delta)

    def test_geometry_mismatch_rejected(self):
        delta = np.zeros((3, 16), dtype=np.int64)
        frame = p.encode_dcn_debt(1, delta)
        with pytest.raises(p.ProtocolError, match="geometry"):
            p.parse_dcn(frame[p.HEADER_SIZE:], 4, 16, 0)

    def test_subwindow_mismatch_rejected(self):
        """Periods are denominated in sub_us units: a peer mid-window-
        migration (different sub_us) must be refused, not renumbered."""
        from ratelimiter_tpu import InvalidConfigError

        periods = np.array([5], dtype=np.int64)
        slabs = np.zeros((1, 3, 16), dtype=np.int32)
        frame = p.encode_dcn_slabs(1, periods, slabs, 1_000_000)
        with pytest.raises(InvalidConfigError, match="sub-window"):
            p.parse_dcn(frame[p.HEADER_SIZE:], 3, 16, 500_000)

    def test_dcn_frames_may_exceed_request_cap(self):
        # A d=4 w=65536 debt delta is 2 MiB > MAX_FRAME; the DCN type has
        # its own bound — but ONLY for servers that opted into DCN.
        delta = np.zeros((4, 65536), dtype=np.int64)
        frame = p.encode_dcn_debt(1, delta)
        length, type_, _ = p.parse_header(frame[:p.HEADER_SIZE],
                                          allow_dcn=True)
        assert length > p.MAX_FRAME and type_ == p.T_DCN_PUSH
        with pytest.raises(p.ProtocolError):
            p.parse_header(frame[:p.HEADER_SIZE])  # plain deployments


def _server_on_thread(limiter, dcn=True):
    """A live asyncio server on a background loop; returns (srv, loop)."""
    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    srv = RateLimitServer(limiter, "127.0.0.1", 0, dcn=dcn)
    asyncio.run_coroutine_threadsafe(srv.start(), loop).result(10)
    return srv, loop, t


def _stop(srv, loop, t):
    asyncio.run_coroutine_threadsafe(srv.shutdown(), loop).result(10)
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=10)
    loop.close()


class TestPushOverTcp:
    """Real protocol frames over TCP between two servers (one OS process,
    two event loops — the wire path is identical to cross-process; the
    subprocess test below covers process isolation)."""

    def _pod(self, algo, **sketch_kw):
        clock = ManualClock(T0)
        cfg = Config(algorithm=algo, limit=10, window=6.0,
                     sketch=SketchParams(depth=3, width=256, sub_windows=6,
                                         **sketch_kw))
        return create_limiter(cfg, backend="sketch", clock=clock), clock

    def test_windowed_slabs_push(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, ca = self._pod(Algorithm.TPU_SKETCH)
        b, cb = self._pod(Algorithm.TPU_SKETCH)
        srv, loop, t = _server_on_thread(b)
        try:
            assert a.allow_n("k", 10).allowed      # drain on A
            ca.advance(1.0)
            cb.advance(1.0)
            a.allow("warm")                        # complete A's sub-window
            b.allow("warm")                        # roll B to the same period
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)])
            assert pusher.sync_once() == 1
            assert not b.allow("k").allowed        # A's history visible on B
            # Watermark: nothing new -> nothing pushed.
            assert pusher.sync_once() == 0
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_bucket_debt_push(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, _ca = self._pod(Algorithm.TOKEN_BUCKET)
        b, _cb = self._pod(Algorithm.TOKEN_BUCKET)
        srv, loop, t = _server_on_thread(b)
        try:
            assert a.allow_n("k", 10).allowed
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)])
            assert pusher.sync_once() == 1
            assert not b.allow("k").allowed
            assert pusher.sync_once() == 0         # acc zeroed at export
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_dcn_frames_rejected_when_not_enabled(self):
        """A plain server (dcn=False, the default) refuses T_DCN_PUSH:
        small frames get a typed error, oversized headers drop the
        connection before buffering (memory-DoS bound)."""
        import struct

        a, _ = self._pod(Algorithm.TOKEN_BUCKET)
        b, _ = self._pod(Algorithm.TOKEN_BUCKET)
        srv, loop, t = _server_on_thread(b, dcn=False)
        try:
            a.allow_n("k", 5)
            from ratelimiter_tpu.parallel.dcn import export_debt
            from ratelimiter_tpu.serving.dcn_peer import _PeerConn

            delta = export_debt(a)
            peer = _PeerConn("127.0.0.1", srv.port)
            with pytest.raises(Exception, match="not enabled"):
                peer.push(p.encode_dcn_debt(1, delta), 1)
            peer.close()
            # Oversized header claiming T_DCN_PUSH: connection dropped,
            # nothing buffered.
            with socket.create_connection(("127.0.0.1", srv.port)) as sk:
                sk.sendall(struct.pack("<IBQ", 48 << 20, p.T_DCN_PUSH, 2))
                sk.settimeout(5)
                assert sk.recv(16) == b""          # server closed it
            # And the key is still fresh on B (nothing merged).
            assert b.allow("k").allowed
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_no_echo_of_foreign_slabs(self):
        """Bidirectional pushers must not re-export merged foreign data
        (the contamination double-count): after A->B then B->A, A's view
        of the key equals the true global count, not double."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, ca = self._pod(Algorithm.TPU_SKETCH)
        b, cb = self._pod(Algorithm.TPU_SKETCH)
        srv_a, loop_a, ta = _server_on_thread(a)
        srv_b, loop_b, tb = _server_on_thread(b)
        try:
            a.allow_n("k", 4)                      # 4 of 10 on A
            ca.advance(1.0)
            cb.advance(1.0)
            a.allow("warm")
            b.allow("warm")
            push_a = DcnPusher(a, [("127.0.0.1", srv_b.port)])
            push_b = DcnPusher(b, [("127.0.0.1", srv_a.port)])
            assert push_a.sync_once() == 1         # A's slab lands on B
            assert push_b.sync_once() == 1         # B exports its "warm"
            # B's export must NOT have echoed A's 4 back: A still sees
            # exactly 4 consumed, so 6 remain.
            assert a.allow_n("k", 6).allowed
            assert not a.allow("k").allowed
            push_a.stop()
            push_b.stop()
        finally:
            _stop(srv_a, loop_a, ta)
            _stop(srv_b, loop_b, tb)
        a.close()
        b.close()

    def test_debt_delta_restored_on_total_push_failure(self):
        """A partitioned pusher re-accumulates the delta instead of
        dropping an interval of traffic per cycle."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, _ = self._pod(Algorithm.TOKEN_BUCKET)
        b, _ = self._pod(Algorithm.TOKEN_BUCKET)
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        pusher = DcnPusher(a, [("127.0.0.1", dead_port)])
        a.allow_n("k", 10)
        assert pusher.sync_once() == 0             # partition: restored
        # Point at a live peer: the SAME traffic ships on the next cycle.
        srv, loop, t = _server_on_thread(b)
        try:
            pusher.peers[0].port = srv.port
            assert pusher.sync_once() == 1
            assert not b.allow("k").allowed
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_slab_pushes_chunk_under_frame_cap(self):
        """Many pending periods split across frames (one ring's worth of
        large slabs would exceed MAX_DCN_FRAME in a single frame)."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, ca = self._pod(Algorithm.TPU_SKETCH)
        b, cb = self._pod(Algorithm.TPU_SKETCH)
        srv, loop, t = _server_on_thread(b)
        try:
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)])
            pusher._payload_budget = pusher._slab_bytes  # force 1 slab/frame
            for i in range(4):                     # 4 completed periods
                a.allow_n(f"k{i}", 10)
                ca.advance(1.0)
                cb.advance(1.0)
            a.allow("warm")
            b.allow("warm")
            assert pusher.sync_once() == 1
            assert pusher.pushes_ok >= 4           # one frame per period
            for i in range(4):
                assert not b.allow(f"k{i}").allowed
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_oversized_geometry_rejected_at_construction(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=10, window=6.0,
                     sketch=SketchParams(depth=16, width=1 << 21,
                                         sub_windows=6))
        lim = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
        with pytest.raises(ValueError, match="too large"):
            DcnPusher(lim, [("127.0.0.1", 1)])
        lim.close()

    def test_push_failure_counted_not_fatal(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, _ = self._pod(Algorithm.TOKEN_BUCKET)
        a.allow_n("k", 3)
        # Nobody listening on this port.
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        pusher = DcnPusher(a, [("127.0.0.1", dead_port)])
        assert pusher.sync_once() == 0
        assert pusher.pushes_failed == 1
        pusher.stop()
        a.close()


class TestDcnAuth:
    """T_DCN_PUSH HMAC envelope (ADVICE r4: an open serving port accepting
    pushes is a targeted false-deny lever; the secret closes it)."""

    def _pod(self, **kw):
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=10, window=6.0,
                     sketch=SketchParams(depth=3, width=256, sub_windows=6))
        return create_limiter(cfg, backend="sketch", clock=clock)

    def test_matching_secret_accepted(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            a.allow_n("k", 10)
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)], secret="s3cret")
            assert pusher.sync_once() == 1
            assert not b.allow("k").allowed
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_unauthenticated_push_rejected(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            a.allow_n("k", 10)
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)])  # no secret
            assert pusher.sync_once() == 0
            assert pusher.pushes_failed == 1
            assert b.allow("k").allowed            # nothing merged
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_wrong_secret_rejected(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            a.allow_n("k", 10)
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)], secret="wrong")
            assert pusher.sync_once() == 0
            assert b.allow("k").allowed
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_tagged_push_to_open_server_accepted(self):
        """An open (no-secret) receiver strips and ignores the tag, so a
        fleet can roll the secret out one pod at a time."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)      # no secret on receiver
        try:
            a.allow_n("k", 10)
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)], secret="s3cret")
            assert pusher.sync_once() == 1
            assert not b.allow("k").allowed
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()


class TestDcnReplay:
    """Replay protection for authenticated pushes (ADR-007): the RLA2
    envelope carries a per-sender monotonic sequence INSIDE the HMAC;
    receivers reject stale/duplicate values — a replayed push is a
    counter-mass injection lever (targeted false denies)."""

    def _pod(self):
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=10, window=6.0,
                     sketch=SketchParams(depth=3, width=256, sub_windows=6))
        return create_limiter(cfg, backend="sketch", clock=clock)

    def _push_frame(self, port, frame, req_id):
        from ratelimiter_tpu.serving.dcn_peer import _PeerConn

        peer = _PeerConn("127.0.0.1", port)
        try:
            peer.push(frame, req_id)
        finally:
            peer.close()

    def test_replayed_frame_rejected(self):
        from ratelimiter_tpu.core.errors import InvalidConfigError
        from ratelimiter_tpu.parallel import dcn

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            a.allow_n("k", 10)
            delta = dcn.export_debt(a)
            seq = int(time.time() * 1e6)
            frame = p.encode_dcn_debt(1, delta, secret="s3cret",
                                      sender=7777, seq=seq)
            self._push_frame(srv.port, frame, 1)       # first copy lands
            with pytest.raises(InvalidConfigError, match="replayed"):
                self._push_frame(srv.port, frame, 1)   # byte-identical replay
            assert srv._dcn_guard.rejected == 1
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_out_of_order_sequence_rejected(self):
        from ratelimiter_tpu.core.errors import InvalidConfigError
        from ratelimiter_tpu.parallel import dcn

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            a.allow_n("k", 5)
            delta = dcn.export_debt(a)
            seq = int(time.time() * 1e6)
            newer = p.encode_dcn_debt(1, delta, secret="s3cret",
                                      sender=42, seq=seq)
            older = p.encode_dcn_debt(2, delta, secret="s3cret",
                                      sender=42, seq=seq - 10)
            self._push_frame(srv.port, newer, 1)
            with pytest.raises(InvalidConfigError, match="replayed"):
                self._push_frame(srv.port, older, 2)
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_stale_first_contact_rejected(self):
        """An unknown sender whose sequence is older than the freshness
        window (a captured stream from a dead incarnation) is refused —
        the documented residual is bounded to that window."""
        from ratelimiter_tpu.core.errors import InvalidConfigError
        from ratelimiter_tpu.parallel import dcn

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            a.allow_n("k", 5)
            delta = dcn.export_debt(a)
            stale_seq = int((time.time() - 3600.0) * 1e6)
            frame = p.encode_dcn_debt(1, delta, secret="s3cret",
                                      sender=99, seq=stale_seq)
            with pytest.raises(InvalidConfigError, match="stale"):
                self._push_frame(srv.port, frame, 1)
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_legacy_unsequenced_envelope_rejected_by_secret_server(self):
        """RLA1 (HMAC but no sequence) replays forever, so a receiver
        that requires auth refuses it outright."""
        from ratelimiter_tpu.core.errors import InvalidConfigError
        from ratelimiter_tpu.parallel import dcn

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            a.allow_n("k", 5)
            delta = dcn.export_debt(a)
            legacy = p.encode_dcn_debt(1, delta, secret="s3cret")  # no seq
            with pytest.raises(InvalidConfigError, match="RLA1"):
                self._push_frame(srv.port, legacy, 1)
        finally:
            _stop(srv, loop, t)
        a.close()

    def test_long_running_sender_fresh_to_new_guard(self):
        """The pusher's sequence must TRACK wall-clock micros, not just
        increment: a receiver whose guard state is new (restart, late
        join, eviction) applies the first-contact freshness floor, and a
        sender that had merely counted up from its start time would look
        permanently stale after max_age_s of uptime."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a = self._pod()
        pusher = DcnPusher(a, [], secret="s3cret")
        for _ in range(50):                      # long-running incarnation
            pusher._next_seq()
        guard = p.DcnReplayGuard(max_age_s=300.0)
        guard.check(pusher._sender, pusher._next_seq())   # must not raise
        assert guard.rejected == 0
        # And still strictly increasing (replay of the previous frame is
        # caught even when two frames share a microsecond).
        s1, s2 = pusher._next_seq(), pusher._next_seq()
        assert s2 > s1
        a.close()

    def test_pusher_cycles_pass_the_guard(self):
        """A real DcnPusher's consecutive cycles carry strictly
        increasing sequences, so the guard never trips on the happy
        path — including multi-frame (chunked) cycles."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher

        a, b = self._pod(), self._pod()
        srv, loop, t = _server_on_thread(b)
        srv.dcn_secret = "s3cret"
        try:
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)],
                               secret="s3cret")
            a.allow_n("k", 4)
            assert pusher.sync_once() == 1
            a.allow_n("k2", 3)
            assert pusher.sync_once() == 1
            assert pusher.pushes_failed == 0
            assert srv._dcn_guard.rejected == 0
            pusher.stop()
        finally:
            _stop(srv, loop, t)
        a.close()


class TestNativeDcn:
    """The native (C++) front door receives T_DCN_PUSH via its dcn
    callback — a multi-pod deployment needs only --native servers
    (VERDICT r4 item 5)."""

    def _pod(self, algo=Algorithm.TPU_SKETCH):
        clock = ManualClock(T0)
        cfg = Config(algorithm=algo, limit=10, window=6.0,
                     sketch=SketchParams(depth=3, width=256, sub_windows=6))
        return create_limiter(cfg, backend="sketch", clock=clock), clock

    @pytest.fixture(autouse=True)
    def _need_native(self):
        from ratelimiter_tpu.serving.native_server import (
            native_server_available,
        )

        if not native_server_available():
            pytest.skip("needs g++ for the native server")

    def test_windowed_slabs_push_to_native_door(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
        )

        a, ca = self._pod()
        b, cb = self._pod()
        srv = NativeRateLimitServer(b, "127.0.0.1", 0, dcn=True)
        srv.start()
        try:
            assert a.allow_n("k", 10).allowed
            ca.advance(1.0)
            cb.advance(1.0)
            a.allow("warm")
            b.allow("warm")
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)])
            assert pusher.sync_once() == 1
            assert not b.allow("k").allowed
            pusher.stop()
        finally:
            srv.shutdown()
        a.close()
        b.close()

    def test_debt_push_to_native_door_with_secret(self):
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
        )

        a, _ = self._pod(Algorithm.TOKEN_BUCKET)
        b, _ = self._pod(Algorithm.TOKEN_BUCKET)
        srv = NativeRateLimitServer(b, "127.0.0.1", 0, dcn=True,
                                    dcn_secret="s3cret")
        srv.start()
        try:
            a.allow_n("k", 10)
            bad = DcnPusher(a, [("127.0.0.1", srv.port)])  # untagged
            assert bad.sync_once() == 0
            bad.stop()
            # Delta was restored on total failure; the tagged pusher
            # ships the SAME traffic.
            good = DcnPusher(a, [("127.0.0.1", srv.port)], secret="s3cret")
            assert good.sync_once() == 1
            assert not b.allow("k").allowed
            good.stop()
        finally:
            srv.shutdown()
        a.close()
        b.close()

    def test_native_door_without_dcn_refuses_pushes(self):
        from ratelimiter_tpu.serving.dcn_peer import _PeerConn
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
        )
        from ratelimiter_tpu.parallel.dcn import export_debt

        a, _ = self._pod(Algorithm.TOKEN_BUCKET)
        b, _ = self._pod(Algorithm.TOKEN_BUCKET)
        srv = NativeRateLimitServer(b, "127.0.0.1", 0)   # dcn off
        srv.start()
        try:
            a.allow_n("k", 5)
            delta = export_debt(a)
            peer = _PeerConn("127.0.0.1", srv.port)
            with pytest.raises(Exception, match="not enabled"):
                peer.push(p.encode_dcn_debt(1, delta), 1)
            peer.close()
            assert b.allow("k").allowed
        finally:
            srv.shutdown()
        a.close()
        b.close()

    def test_large_frame_exceeding_request_cap_accepted(self):
        """A production-geometry push (> the 4 MiB plain read-buffer
        bound, here an 8 MiB debt delta) must survive the native door's
        IO loop — the backpressure cap is type-aware only on DCN-enabled
        servers (code-review r5 finding: the old flat 4*MAX_FRAME guard
        killed the connection mid-frame)."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
        )

        def big_pod():
            clock = ManualClock(T0)
            cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=10,
                         window=6.0,
                         sketch=SketchParams(depth=4, width=1 << 18,
                                             sub_windows=6))
            return create_limiter(cfg, backend="sketch", clock=clock)

        a, b = big_pod(), big_pod()
        srv = NativeRateLimitServer(b, "127.0.0.1", 0, dcn=True)
        srv.start()
        try:
            a.allow_n("k", 10)
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)])
            assert pusher.sync_once() == 1
            assert not b.allow("k").allowed
            pusher.stop()
        finally:
            srv.shutdown()
        a.close()
        b.close()

    def test_push_merges_into_every_shard(self):
        """Foreign mass must be visible no matter which shard owns the
        key (ADVICE r4 medium: shard-0-only export/merge loses
        (N-1)/N of traffic)."""
        from ratelimiter_tpu.serving.dcn_peer import DcnPusher
        from ratelimiter_tpu.serving.native_server import (
            NativeRateLimitServer,
        )

        a, _ = self._pod(Algorithm.TOKEN_BUCKET)
        b, _ = self._pod(Algorithm.TOKEN_BUCKET)
        srv = NativeRateLimitServer(b, "127.0.0.1", 0, shards=4, dcn=True)
        srv.start()
        try:
            keys = [f"user:{i}" for i in range(8)]
            shards_hit = {srv.shard_of(k) for k in keys}
            assert len(shards_hit) > 1             # keys span shards
            for k in keys:
                a.allow_n(k, 10)
            pusher = DcnPusher(a, [("127.0.0.1", srv.port)])
            assert pusher.sync_once() == 1
            with Client(port=srv.port) as c:
                for k in keys:                     # every shard denies
                    assert not c.allow(k).allowed
                assert c.allow("fresh").allowed
            pusher.stop()
        finally:
            srv.shutdown()
        a.close()
        b.close()


@pytest.mark.slow
class TestTwoProcesses:
    def test_cross_process_bucket_convergence(self):
        """Two OS processes running the real server binary converge: a key
        drained on pod A is denied on pod B within one push interval."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        # Force CPU in the subprocesses: a chip belongs to one process
        # at a time, so two servers cannot share an inherited
        # accelerator, and it is beside the point here.
        env["JAX_PLATFORMS"] = "cpu"


        port_a, port_b = free_port(), free_port()
        common = [sys.executable, "-m", "ratelimiter_tpu.serving",
                  "--backend", "sketch", "--algorithm", "token_bucket",
                  "--limit", "10", "--window", "60",
                  "--sketch-depth", "3", "--sketch-width", "256",
                  "--no-prewarm", "--dcn-interval", "0.2"]
        pa = subprocess.Popen(
            common + ["--port", str(port_a),
                      "--dcn-peer", f"127.0.0.1:{port_b}"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        pb = subprocess.Popen(
            common + ["--port", str(port_b),
                      "--dcn-peer", f"127.0.0.1:{port_a}"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            assert "serving" in pa.stdout.readline()
            assert "serving" in pb.stdout.readline()
            with Client(port=port_a, timeout=60.0) as ca:
                assert ca.allow_n("k", 10).allowed   # drain on A
            # Poll with a bounded probe budget instead of one fixed
            # sleep (jit-compile noise under machine load made a 3 s
            # sleep flaky): <= 8 B-local probes can never exhaust the
            # limit of 10 by themselves, so a denial PROVES A's debt
            # landed.
            with Client(port=port_b, timeout=60.0) as cb:
                res = None
                for _ in range(8):
                    time.sleep(1.0)
                    res = cb.allow("k")
                    if not res.allowed:
                        break
                assert res is not None and not res.allowed
                assert res.retry_after > 0
                # Fresh keys still fine on B.
                assert cb.allow("other").allowed
            for proc in (pa, pb):
                proc.send_signal(signal.SIGTERM)
                assert proc.wait(timeout=20) == 0
        finally:
            for proc in (pa, pb):
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()

    def test_cross_process_windowed_slab_convergence_native(self):
        """The windowed slab path (watermarks, foreign-record
        subtraction, chunking) between two real server binaries — both
        running the NATIVE front door, pod A with 2 dispatch shards, so
        the whole multi-pod surface (per-shard pushers, C++ T_DCN_PUSH
        receive, HMAC auth) is exercised end to end (VERDICT r4 items
        5+6)."""
        from ratelimiter_tpu.serving.native_server import (
            native_server_available,
        )

        if not native_server_available():
            pytest.skip("needs g++ for the native server")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        env["JAX_PLATFORMS"] = "cpu"
        env["RATELIMITER_TPU_DCN_SECRET"] = "two-proc-secret"


        port_a, port_b = free_port(), free_port()
        common = [sys.executable, "-m", "ratelimiter_tpu.serving",
                  "--backend", "sketch", "--algorithm", "sliding_window",
                  "--limit", "10", "--window", "30",
                  "--sub-windows", "30",
                  "--sketch-depth", "3", "--sketch-width", "256",
                  "--no-prewarm", "--native", "--dcn-interval", "0.2"]
        pa = subprocess.Popen(
            common + ["--port", str(port_a), "--shards", "2",
                      "--dcn-peer", f"127.0.0.1:{port_b}"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        pb = subprocess.Popen(
            common + ["--port", str(port_b),
                      "--dcn-peer", f"127.0.0.1:{port_a}"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            assert "serving" in pa.stdout.readline()
            assert "serving" in pb.stdout.readline()
            keys = [f"user:{i}" for i in range(4)]
            with Client(port=port_a, timeout=60.0) as ca:
                for k in keys:
                    assert ca.allow_n(k, 10).allowed   # drain on A
            # Slabs only ship once their sub-window (1 s) completes, and
            # completion is driven by later dispatches: keep warm traffic
            # flowing on both pods while the exchange happens. Probe each
            # key at most 8 times: 8 B-local admissions < limit 10, so a
            # denial on B PROVES A's 10/10 drain landed (B alone could
            # never deny within the probe budget).
            converged = False
            with Client(port=port_a, timeout=60.0) as ca, \
                    Client(port=port_b, timeout=60.0) as cb:
                for _ in range(8):
                    ca.allow("warm-a")
                    cb.allow("warm-b")
                    time.sleep(1.0)
                    if all(not cb.allow(k).allowed for k in keys):
                        converged = True
                        break
                assert converged, "A's slabs never became visible on B"
                # Fresh keys unaffected.
                assert cb.allow("fresh").allowed
            for proc in (pa, pb):
                proc.send_signal(signal.SIGTERM)
                assert proc.wait(timeout=20) == 0
        finally:
            for proc in (pa, pb):
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
