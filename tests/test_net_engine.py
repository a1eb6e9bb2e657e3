"""Multi-ring network engine tests (ISSUE-20, ADR-026).

The native door's wire path is N sharded io rings behind one NetEngine
interface with two backends: portable epoll and raw-syscall io_uring
selected by a startup probe. These tests pin the properties the PR
promises:

* engine PARITY — the reply byte stream is bit-identical across
  backends and ring counts (same pin as tcp==uds==shm in ADR-025);
* the io_uring path NEVER silently skips — when the kernel (or
  seccomp) refuses the probe, the server records an asserted
  downgrade in stats()["net"] and serves on epoll, and the test
  asserts THAT record instead of skipping;
* robustness — kill -9 / RST mid-frame, slow-loris partial frames
  spread across ring shards, one firehose connection cannot starve
  the ring (bounded read budget per wakeup);
* reply coalescing — the writev_frames / writev_calls counters prove
  frames ride vectored writes, and the scatter-gather encoder for
  T_RESULT_BATCH is byte-identical to the joined form by construction.
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from contextlib import contextmanager

import pytest

from ratelimiter_tpu import Algorithm, Config, ManualClock, create_limiter
from ratelimiter_tpu.serving import Client
from ratelimiter_tpu.serving import protocol as p
from ratelimiter_tpu.serving.native_server import (
    NativeRateLimitServer,
    native_server_available,
)

needs_native = pytest.mark.skipif(
    not native_server_available(), reason="needs g++ for the native server")


def _mk_limiter(limit=100, window=60.0, backend="exact", **kw):
    clock = ManualClock(1_700_000_000.0)
    cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=limit,
                 window=window, **kw)
    return create_limiter(cfg, backend=backend, clock=clock), clock


@contextmanager
def running_native(limiter, host="127.0.0.1", **kw):
    srv = NativeRateLimitServer(limiter, host, 0, **kw)
    srv.start()
    try:
        yield srv
    finally:
        srv.shutdown()


def _net(srv) -> dict:
    return srv.transport_stats()["net"]


def _assert_engine_record(net: dict, requested: str) -> None:
    """The probe contract: an explicit uring request either runs uring
    (probe passed) or serves on epoll with the refusal RECORDED — the
    caller asserts the record, never skips."""
    assert net["rings"] >= 1
    if requested == "epoll":
        assert net["engine"] == "epoll"
        assert net["uring_probe"] == "off"
        return
    assert net["uring_probe"] in ("pass", "fail")
    if net["uring_probe"] == "pass":
        assert net["engine"] == "uring"
    else:
        assert net["engine"] == "epoll"
        assert net["uring_probe_err"], (
            "a failed probe must say WHY (seccomp/ENOSYS/...)")


def _read_frame(sock: socket.socket) -> bytes:
    hdr = b""
    while len(hdr) < 4:
        d = sock.recv(4 - len(hdr))
        assert d, "unexpected EOF mid-header"
        hdr += d
    (length,) = struct.unpack("<I", hdr)
    body = b""
    while len(body) < length:
        d = sock.recv(length - len(body))
        assert d, "unexpected EOF mid-frame"
        body += d
    return hdr + body


# --------------------------------------------------- engine selection

@needs_native
class TestEngineSelection:
    def test_epoll_single_ring_pre_pr_shape(self):
        """--net-engine epoll --io-rings 1 is the pre-ISSUE-20 wire
        topology: one event loop, no probe run at all."""
        lim, _ = _mk_limiter()
        with running_native(lim, net_engine="epoll", io_rings=1) as srv:
            with Client(port=srv.port) as c:
                assert c.allow("k").allowed
            net = _net(srv)
            assert net == {**net, "engine": "epoll", "rings": 1,
                           "uring_probe": "off"}
            assert net["recv_calls"] > 0 and net["wait_calls"] > 0
        lim.close()

    def test_uring_request_never_silently_skips(self):
        lim, _ = _mk_limiter()
        with running_native(lim, net_engine="uring", io_rings=2) as srv:
            net = _net(srv)
            _assert_engine_record(net, "uring")
            assert net["rings"] == 2
            with Client(port=srv.port) as c:
                assert c.allow("k").allowed
                assert not all(c.allow("k").allowed for _ in range(200))
        lim.close()

    def test_auto_records_probe_result(self):
        lim, _ = _mk_limiter()
        with running_native(lim, net_engine="auto") as srv:
            _assert_engine_record(_net(srv), "auto")
            with Client(port=srv.port) as c:
                assert c.allow("k").allowed
        lim.close()

    def test_invalid_engine_rejected(self):
        lim, _ = _mk_limiter()
        with pytest.raises(ValueError, match="net_engine"):
            NativeRateLimitServer(lim, "127.0.0.1", 0,
                                  net_engine="kqueue")
        lim.close()

    def test_healthz_surface_carries_engine(self):
        lim, _ = _mk_limiter()
        with running_native(lim, net_engine="auto", io_rings=2) as srv:
            st = srv.transport_stats()
            assert st["net"]["rings"] == 2
            assert st["net"]["engine"] in ("epoll", "uring")
        lim.close()


# ------------------------------------------------------- byte parity

@needs_native
class TestEngineParity:
    """Frame-for-frame bit-identical reply streams across backends and
    ring counts, driven lockstep so ordering is deterministic. The
    uring variant runs EVEN when the kernel refuses io_uring — the
    server downgrades with an asserted record (see
    _assert_engine_record), so the parity pin holds on every box with
    zero skips."""

    SCRIPT = None  # built once per run

    @classmethod
    def _script(cls):
        if cls.SCRIPT is None:
            frames = []
            for i in range(12):
                frames.append(p.encode_allow_n(i + 1, f"key{i % 3}", 1))
            frames.append(p.encode_allow_batch(
                100, ["alpha", "beta", "gamma"], [2, 1, 3]))
            frames.append(p.encode_reset(101, "key0"))
            for i in range(6):
                frames.append(p.encode_allow_n(200 + i, "post-reset", 2))
            cls.SCRIPT = frames
        return cls.SCRIPT

    def _reply_stream(self, net_engine: str, io_rings: int) -> tuple:
        lim, _ = _mk_limiter(limit=10)
        try:
            with running_native(lim, net_engine=net_engine,
                                io_rings=io_rings) as srv:
                out = []
                with socket.create_connection(("127.0.0.1", srv.port),
                                              timeout=10) as s:
                    s.settimeout(10)
                    for frame in self._script():
                        s.sendall(frame)
                        out.append(_read_frame(s))
                return b"".join(out), _net(srv)
        finally:
            lim.close()

    def test_reply_bytes_identical_across_engines(self):
        base, base_net = self._reply_stream("epoll", 1)
        assert base_net["engine"] == "epoll"
        multi, _ = self._reply_stream("epoll", 4)
        uring, uring_net = self._reply_stream("uring", 3)
        _assert_engine_record(uring_net, "uring")
        assert multi == base, "ring sharding changed wire bytes"
        assert uring == base, (
            f"io_uring backend changed wire bytes "
            f"(engine={uring_net['engine']})")
        # The pinned stream is not vacuous: allows, denies, a batch
        # result, and an OK all appear.
        assert len(base) > 20 * 13


# -------------------------------------------------------- robustness

@needs_native
class TestRobustness:
    @pytest.mark.parametrize("net_engine", ["epoll", "uring"])
    def test_client_death_mid_frame(self, net_engine):
        """A client dying mid-frame — orderly FIN (kill -9: the kernel
        closes the fd) or hard RST (SO_LINGER 0) — must not wedge the
        ring: the half-frame is dropped with the connection and new
        clients are served."""
        lim, _ = _mk_limiter(limit=100000)
        with running_native(lim, net_engine=net_engine,
                            io_rings=2) as srv:
            frame = p.encode_allow_n(7, "victim", 1)
            # FIN mid-frame.
            s1 = socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=10)
            s1.sendall(frame[:len(frame) // 2])
            s1.close()
            # RST mid-frame.
            s2 = socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=10)
            s2.sendall(frame[:len(frame) // 2])
            s2.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                          struct.pack("ii", 1, 0))
            s2.close()
            deadline = time.time() + 10
            while time.time() < deadline:
                with Client(port=srv.port) as c:
                    if c.allow("survivor").allowed:
                        break
                time.sleep(0.05)
            else:
                pytest.fail("server stopped answering after mid-frame "
                            "client death")
        lim.close()

    def test_slow_loris_across_ring_shards(self):
        """Byte-at-a-time senders spread over 4 rings: every dribbled
        frame is eventually answered, and a well-behaved client on the
        same server stays fast throughout."""
        lim, _ = _mk_limiter(limit=100000)
        with running_native(lim, net_engine="epoll", io_rings=4) as srv:
            results = {}

            def loris(idx: int):
                frame = p.encode_allow_n(idx, f"loris{idx}", 1)
                with socket.create_connection(
                        ("127.0.0.1", srv.port), timeout=15) as s:
                    s.settimeout(15)
                    for b in frame:
                        s.sendall(bytes([b]))
                        time.sleep(0.002)
                    results[idx] = _read_frame(s)

            threads = [threading.Thread(target=loris, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            # The fast lane stays fast while 8 loris conns dribble.
            t0 = time.time()
            with Client(port=srv.port) as c:
                for _ in range(20):
                    assert c.allow("fast").allowed
            fast_elapsed = time.time() - t0
            for t in threads:
                t.join(timeout=30)
            assert len(results) == 8, "a dribbled frame went unanswered"
            assert fast_elapsed < 5.0, (
                f"well-behaved client stalled {fast_elapsed:.1f}s "
                "behind slow-loris peers")
        lim.close()

    def test_firehose_cannot_starve_the_ring(self):
        """One connection pipelining a huge burst must not starve a
        neighbour pinned to the same ring (per-wakeup read budget)."""
        lim, _ = _mk_limiter(limit=1000000)
        with running_native(lim, net_engine="epoll", io_rings=1,
                            max_batch=4096) as srv:
            hose = socket.create_connection(("127.0.0.1", srv.port),
                                            timeout=10)
            hose.settimeout(10)
            burst = b"".join(p.encode_allow_n(i, "hose", 1)
                             for i in range(2000))
            hose.sendall(burst)
            t0 = time.time()
            with Client(port=srv.port) as c:
                assert c.allow("neighbour").allowed
            assert time.time() - t0 < 5.0, "firehose starved the ring"
            # The hose still gets every reply (nothing dropped).
            got = 0
            buf = b""
            while got < 2000:
                d = hose.recv(1 << 16)
                assert d, "EOF before all firehose replies"
                buf += d
                while len(buf) >= 4:
                    (ln,) = struct.unpack_from("<I", buf)
                    if len(buf) < 4 + ln:
                        break
                    buf = buf[4 + ln:]
                    got += 1
            hose.close()
        lim.close()


# ------------------------------------------------- vectored replies

@needs_native
class TestWritevCoalescing:
    def test_writev_frames_counter_proves_batching(self):
        """Pipelined burst on one connection: every reply frame rides a
        vectored write (writev_frames counts them) and frames outnumber
        sendmsg calls — the batch factor the
        rate_limiter_net_writev_frames metric exports."""
        lim, _ = _mk_limiter(limit=1000000)
        with running_native(lim, net_engine="epoll", io_rings=1,
                            max_batch=512, max_delay=0.005) as srv:
            n = 300
            with socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=10) as s:
                s.settimeout(10)
                s.sendall(b"".join(p.encode_allow_n(i, "burst", 1)
                                   for i in range(n)))
                for _ in range(n):
                    _read_frame(s)
            # The io thread counts a vectored write AFTER the syscall
            # returns, so the last reply can reach this thread before its
            # frames are counted (2 runs in 80 under eight parallel
            # loops): give the counter a moment, then hold it to the same
            # bound.
            deadline = time.monotonic() + 2.0
            net = _net(srv)
            while net["writev_frames"] < n and time.monotonic() < deadline:
                time.sleep(0.01)
                net = _net(srv)
            assert net["writev_frames"] >= n
            assert net["writev_calls"] >= 1
            assert net["writev_calls"] < net["writev_frames"], (
                "no coalescing happened: every frame paid its own "
                "write syscall")
        lim.close()


class TestBatchViewsEncoder:
    def test_views_join_is_the_single_buffer_frame(self):
        """The scatter-gather T_RESULT_BATCH encoder IS the framing
        source: joining its parts must reproduce encode_result_batch
        byte-for-byte (the asyncio door's writelines path cannot
        drift), and the parts round-trip through the parser."""
        results = [p.Result(allowed=(i % 3 != 0), limit=50,
                            remaining=50 - i, retry_after=0.5 * i,
                            reset_at=1e9 + i, fail_open=(i == 4))
                   for i in range(9)]
        views = p.encode_result_batch_views(41, 50, results)
        assert len(views) == 1 + len(results)
        joined = b"".join(views)
        assert joined == p.encode_result_batch(41, 50, results)
        length, type_, req_id = struct.unpack_from("<IBQ", joined)
        assert type_ == p.T_RESULT_BATCH and req_id == 41
        parsed = p.parse_result_batch(joined[13:])
        assert [r.allowed for r in parsed] == [
            r.allowed for r in results]
        assert [r.fail_open for r in parsed] == [
            r.fail_open for r in results]


# ------------------------------------------------ shm over the rings

@needs_native
class TestShmOverEngines:
    def test_shm_handshake_over_uring(self):
        """The shm ctrl listener and doorbell eventfds ride the owning
        ring on EVERY backend: the full hello → ctrl connect → fd-pass
        handshake and ring traffic must work with the uring engine (or
        its asserted epoll downgrade) exactly as on epoll."""
        lim, _ = _mk_limiter(limit=100000)
        with running_native(lim, shm=True, net_engine="uring",
                            io_rings=2) as srv:
            _assert_engine_record(_net(srv), "uring")
            with Client(port=srv.port, transport="shm") as c:
                for i in range(10):
                    assert c.allow(f"k{i}").allowed
                res = c.allow_batch(["x", "y"], [2, 3])
                assert all(r.allowed for r in res)
            st = srv.transport_stats()
            assert st["connections"]["shm"] == 1
            assert st["shm"]["records_in"] >= 11
        lim.close()
