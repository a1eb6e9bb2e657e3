"""A capture's wait scales with what it holds: ``runner.fetch_profile``
waits for /debug/profile's body while the gateway's heartbeat keeps
arriving, up to ``CAPTURE_CEILING_S``, and ``runner.capture_of`` lets no
traced run go on without a capture. A stub gateway on a faked clock:
each heartbeat is 5 s of it, so 200 s of stop_trace take milliseconds."""

import json
import socket
import threading
import time

import pytest

from chipbench import runner

BEAT_S = 5.0


class Clock:
    """The faked clock: the stub gateway moves it, the runner reads it."""

    def __init__(self):
        self.now = 1000.0

    def __call__(self) -> float:
        return self.now


class StubGateway:
    """Answers one GET as /debug/profile does: 200 and headers at once,
    ``beats`` spaces (each after BEAT_S of the faked clock), then
    ``body``, then the close that ends it. ``stall_s`` of REAL silence
    before the body stands for a server that died mid-capture."""

    def __init__(self, clock, beats: int, body: bytes, stall_s: float = 0.0):
        self.clock, self.beats, self.body = clock, beats, body
        self.stall_s = stall_s
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(1)
        self.port = self.sock.getsockname()[1]
        self.request = b""
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self) -> None:
        conn, _ = self.sock.accept()
        with conn:
            while b"\r\n\r\n" not in self.request:
                self.request += conn.recv(4096)
            conn.sendall(b"HTTP/1.0 200 OK\r\nContent-Type: application/"
                         b"json\r\nConnection: close\r\n\r\n")
            try:
                for _ in range(self.beats):
                    self.clock.now += BEAT_S
                    conn.sendall(b" ")
                    time.sleep(0.001)      # let the reader see each one
                time.sleep(self.stall_s)
                conn.sendall(self.body)
            except OSError:                # the client gave up: fine
                pass

    def close(self) -> None:
        self.thread.join(timeout=10)
        self.sock.close()


def fetch(gateway, clock) -> tuple:
    box: dict = {}
    thread = threading.Thread(target=runner.fetch_profile,
                              args=(gateway.port, box, clock))
    thread.start()
    return box, thread


CAPTURE = {"ok": True, "dir": "/nowhere", "seconds": 5.0, "files": []}


def test_the_ceiling_is_four_of_the_largest_capture_on_record():
    assert runner.CAPTURE_CEILING_S >= 4 * 104.7
    assert runner.HEARTBEAT_GAP_S > 5 * BEAT_S


def test_a_capture_that_outlives_the_old_150_s_join_is_still_read():
    clock = Clock()
    gateway = StubGateway(clock, beats=40, body=json.dumps(CAPTURE).encode())
    box, thread = fetch(gateway, clock)
    assert runner.capture_of(box, thread) == CAPTURE
    assert 150 < box["waited_s"] == 40 * BEAT_S
    assert b"/debug/profile?seconds=" in gateway.request
    gateway.close()


def test_past_the_ceiling_the_run_fails_and_says_how_long_it_waited():
    clock = Clock()
    beats = int(runner.CAPTURE_CEILING_S / BEAT_S) + 40
    gateway = StubGateway(clock, beats=beats,
                          body=json.dumps(CAPTURE).encode())
    box, thread = fetch(gateway, clock)
    with pytest.raises(runner.RunFailure) as err:
        runner.capture_of(box, thread)
    assert "profile" not in box
    said = str(err.value)
    assert "stop_trace" in said and "heartbeats" in said
    waited = box["waited_s"]
    assert runner.CAPTURE_CEILING_S < waited <= beats * BEAT_S
    assert f"waited {waited:.0f} s" in said
    gateway.close()


def test_a_gateway_that_falls_silent_is_not_waited_for(monkeypatch):
    monkeypatch.setattr(runner, "HEARTBEAT_GAP_S", 0.2)
    clock = Clock()
    gateway = StubGateway(clock, beats=3, stall_s=1.0,
                          body=json.dumps(CAPTURE).encode())
    box, thread = fetch(gateway, clock)
    with pytest.raises(runner.RunFailure, match="stop_trace") as err:
        runner.capture_of(box, thread)
    assert "timed out" in str(err.value).lower()
    gateway.close()


@pytest.mark.parametrize("box, said", [
    ({}, "gave no capture"),                       # neither key: the old hole
    ({"profile": {"ok": False, "error": "profiler unavailable: boom"},
      "waited_s": 61.0}, "boom"),
    ({"profile": [], "waited_s": 1.0}, "gave no capture"),
    ({"error": "ConnectionResetError(104)", "waited_s": 7.0},
     "waited 7 s"),
], ids=["neither", "stop-trace-failed", "not-an-object", "error"])
def test_no_capture_is_a_failed_run_never_a_line_without_device_metrics(
        box, said):
    done = threading.Thread(target=lambda: None)
    done.start()
    with pytest.raises(runner.RunFailure, match="stop_trace") as err:
        runner.capture_of(box, done)
    assert said in str(err.value)


def test_a_thread_that_never_ends_is_given_up_on(monkeypatch):
    monkeypatch.setattr(runner, "CAPTURE_CEILING_S", 0.05)
    monkeypatch.setattr(runner, "HEARTBEAT_GAP_S", 0.05)
    stop = threading.Event()
    hung = threading.Thread(target=stop.wait, daemon=True)
    hung.start()
    with pytest.raises(runner.RunFailure, match="has not answered"):
        runner.capture_of({}, hung)
    stop.set()
    hung.join()
