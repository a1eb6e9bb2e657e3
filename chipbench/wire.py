"""A small blocking client for the serving protocol, the benchmark's own.

The probe, the cold-key check and the metrics scrape go through it, so
the yardstick does not lean on the program's client library. Frames are
little-endian: u32 payload_length | u8 type | u64 request_id | body (the
layouts are in chipbench/loadgen/ratelimiter_client.hpp). One request in
flight at a time.
"""

from __future__ import annotations

import socket
import struct

T_HEALTH, T_METRICS, T_ALLOW_BATCH, T_ALLOW_HASHED = 3, 4, 5, 11
T_HEALTH_R, T_METRICS_R, T_RESULT_BATCH, T_RESULT_HASHED = 131, 132, 133, 136
T_ERROR = 255


class WireError(Exception):
    """The server answered with an error frame, or not in the protocol."""


class Replies:
    """One frame's answers, in request order."""

    def __init__(self, allowed, remaining, policy):
        self.allowed, self.remaining, self.policy = allowed, remaining, policy


class Wire:
    def __init__(self, port: int, host: str = "127.0.0.1",
                 timeout: float = 120.0):
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._rid = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self) -> None:
        self._sock.close()

    def _recv(self, n: int) -> bytes:
        chunks = []
        while n:
            chunk = self._sock.recv(min(n, 1 << 20))
            if not chunk:
                raise WireError("connection closed by the server")
            chunks.append(chunk)
            n -= len(chunk)
        return b"".join(chunks)

    def _roundtrip(self, rtype: int, body: bytes, want: int) -> bytes:
        self._rid += 1
        self._sock.sendall(struct.pack("<IBQ", 9 + len(body), rtype,
                                       self._rid) + body)
        length, got, rid = struct.unpack("<IBQ", self._recv(13))
        resp = self._recv(length - 9)
        if rid != self._rid:
            raise WireError(f"reply to request {rid}, sent {self._rid}")
        if got == T_ERROR:
            code, mlen = struct.unpack_from("<HH", resp)
            raise WireError(f"error frame {code}: "
                            f"{resp[4:4 + mlen].decode(errors='replace')}")
        if got != want:
            raise WireError(f"reply type {got}, want {want}")
        return resp

    def allow_batch(self, keys: list, n: int = 1) -> Replies:
        """One ALLOW_BATCH frame of string keys."""
        body = [struct.pack("<I", len(keys))]
        for key in keys:
            raw = key.encode()
            body.append(struct.pack("<IH", n, len(raw)) + raw)
        resp = self._roundtrip(T_ALLOW_BATCH, b"".join(body), T_RESULT_BATCH)
        (count,) = struct.unpack_from("<I", resp, 8)
        items = [struct.unpack_from("<Bq", resp, 12 + 25 * i)
                 for i in range(count)]
        return Replies([bool(f & 1) for f, _ in items],
                       [r for _, r in items],
                       [bool(f & 2) for f, _ in items])

    def allow_hashed(self, ids: list, n: int = 1) -> Replies:
        """One ALLOW_HASHED frame of raw u64 ids."""
        count = len(ids)
        body = (struct.pack("<I", count) + struct.pack(f"<{count}Q", *ids)
                + struct.pack(f"<{count}I", *([n] * count)))
        resp = self._roundtrip(T_ALLOW_HASHED, body, T_RESULT_HASHED)
        flags, _limit, got = struct.unpack_from("<BqI", resp)
        bits = resp[13:13 + (got + 7) // 8]
        remaining = struct.unpack_from(f"<{got}q", resp, 13 + len(bits))
        return Replies([bool(bits[i >> 3] >> (i & 7) & 1) for i in range(got)],
                       list(remaining), [bool(flags & 2)] * got)

    def metrics(self) -> str:
        resp = self._roundtrip(T_METRICS, b"", T_METRICS_R)
        (n,) = struct.unpack_from("<I", resp)
        return resp[4:4 + n].decode()

    def decisions_total(self) -> int:
        resp = self._roundtrip(T_HEALTH, b"", T_HEALTH_R)
        return struct.unpack_from("<BdQ", resp)[2]
