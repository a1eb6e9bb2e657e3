"""Flight-recorder tracing subsystem (ADR-014) + metrics satellites.

Covers, per ISSUE 7:

* recorder mechanics: ring wraparound, Chrome-trace/Perfetto dump shape;
* span-tree completeness oracle: one MIXED mesh frame through EACH front
  door yields a connected trace (client span -> door stages -> per-slice
  dispatch -> device), with monotone timestamps and no same-stage
  overlap per thread;
* wire propagation: the flagged trace-id extension survives client ->
  server on both doors (and the DCN envelope), HTTP carries
  ``traceparent``;
* tracing-off = zero-overhead smoke: RECORDER is None by default and
  decisions are identical with the recorder on vs off;
* metrics.py satellites: label-value escaping per the Prometheus spec,
  locked reads, the bisect bucket scan, OpenMetrics exemplars;
* the /debug/trace and /debug/profile endpoints' trust boundary;
* ISSUE 25, the native door's ``dispatch`` stage from inside: one ring
  per OS thread whatever the dispatch count, the seven sub-stage spans
  (enter / hash / prep / place / step / finish / leave) per dispatch, the span
  primitive's two sinks and its shared no-op, the door's exact stage
  counters on ``/metrics``;
* ISSUE 37, the threads themselves: the dispatcher's and completer's
  thread-state seconds tile each thread's wall with the recorder off,
  ``gil`` and ``slot`` grow exactly when a thread waits there, thread CPU
  clocks, ``descend`` / ``ascend`` tile the launch callback and ``fetch``
  / ``unpack`` the resolve, and a capture holds no Python-tracer frame
  unless asked (``tracing.profile(python_tracer=True)``).
"""

from __future__ import annotations

import asyncio
import contextlib
import glob
import json
import os
import statistics
import threading
import time
import urllib.request

import numpy as np
import pytest

from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.observability import metrics as m
from ratelimiter_tpu.observability import tracing
from ratelimiter_tpu.parallel import SlicedMeshLimiter
from ratelimiter_tpu.serving import protocol as p
from ratelimiter_tpu.serving.client import AsyncClient, Client
from ratelimiter_tpu.serving.http_gateway import HttpGateway
from ratelimiter_tpu.serving.native_server import (
    NativeRateLimitServer,
    native_server_available,
)
from ratelimiter_tpu.serving.server import RateLimitServer

T0 = 1_700_000_000.0


@pytest.fixture
def recorder():
    """Fresh process recorder per test; always off afterwards so the
    rest of the suite keeps the zero-overhead default."""
    tracing.disable()
    rec = tracing.enable(1024)
    try:
        yield rec
    finally:
        tracing.disable()


def _sketch_cfg(**kw):
    return Config(algorithm=Algorithm.SLIDING_WINDOW, limit=100,
                  window=60.0,
                  sketch=SketchParams(depth=2, width=2048, sub_windows=8),
                  **kw)


# ---------------------------------------------------------------- recorder


class TestRecorder:
    def test_record_and_dump(self, recorder):
        t0 = tracing.now()
        recorder.record("io", t0, t0 + 1000, trace_id=7, shard=3, batch=5)
        spans = recorder.dump()
        assert len(spans) == 1
        s = spans[0]
        assert s["stage"] == "io" and s["trace_id"] == 7
        assert s["shard"] == 3 and s["batch"] == 5
        assert s["t_end_ns"] - s["t_start_ns"] == 1000

    def test_ring_wraparound_keeps_latest(self, recorder):
        cap = recorder.capacity
        base = tracing.now()
        for i in range(cap + 40):
            recorder.record("io", base + i, base + i + 1, trace_id=i + 1)
        spans = [s for s in recorder.dump() if s["stage"] == "io"]
        assert len(spans) == cap
        # The oldest 40 fell off; what remains is the newest cap records
        # in monotone order.
        ids = [s["trace_id"] for s in spans]
        assert ids == list(range(41, cap + 41))

    def test_per_thread_rings_no_interleave_corruption(self, recorder):
        # Rings are keyed by OS thread id: the three writers must be
        # alive at once, or a later one can be handed an earlier one's id
        # (and with it its ring, which then wraps).
        alive = threading.Barrier(3)

        def worker(k):
            alive.wait()
            for i in range(500):
                t = tracing.now()
                recorder.record("launch", t, t + 1, trace_id=k)

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in (1, 2, 3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = recorder.dump()
        per = {k: sum(1 for s in spans if s["trace_id"] == k)
               for k in (1, 2, 3)}
        assert per == {1: 500, 2: 500, 3: 500}

    def test_chrome_trace_is_json_with_events(self, recorder):
        t0 = tracing.now()
        recorder.record("device", t0, t0 + 5000, trace_id=9, batch=2)
        payload = recorder.chrome_trace()
        text = json.dumps(payload)          # Perfetto-loadable JSON
        back = json.loads(text)
        ev = back["traceEvents"][0]
        assert ev["ph"] == "X" and ev["name"] == "device"
        assert ev["args"]["trace_id"] == f"{9:016x}"
        assert ev["dur"] == pytest.approx(5.0)

    def test_off_by_default_and_module_record_noop(self):
        tracing.disable()
        assert tracing.RECORDER is None
        # Guarded module-level record is a no-op, not an error.
        tracing.record("io", 0, 1, trace_id=1)

    def test_stage_summary(self, recorder):
        t0 = tracing.now()
        for i in range(10):
            recorder.record("encode", t0, t0 + 10_000)
        summary = recorder.stage_summary()
        assert summary["encode"]["count"] == 10
        assert summary["encode"]["mean_us"] == pytest.approx(10.0)


class TestSpanStamps:
    """A span's first and last stamps (t_open / t_close): what the
    lane's launch leaves on its ticket (t_lane), so that the native door
    records "descend" and "ascend" around it with no clock read of its
    own."""

    @pytest.mark.parametrize("annotate_on", [False, True],
                             ids=["ring", "ring+traceme"])
    def test_the_stamps_are_the_rows_own(self, recorder, annotate_on):
        tracing.annotate(annotate_on)
        try:
            with tracing.span("prep", batch=3) as sp:
                assert sp.t_open > 0 and sp.t_close == 0
                sp.next("step")
                sp.next("finish")
        finally:
            tracing.annotate(False)
        rows = recorder.dump()
        assert [r["stage"] for r in rows] == ["prep", "step", "finish"]
        assert sp.t_open == rows[0]["t_start_ns"]
        assert sp.t_close == rows[-1]["t_end_ns"]

    def test_a_failing_body_still_closes(self, recorder):
        with pytest.raises(RuntimeError):
            with tracing.span("prep") as sp:
                raise RuntimeError("boom")
        (row,) = recorder.dump()
        assert row["outcome"] == tracing.ERROR
        assert sp.t_close == row["t_end_ns"] >= sp.t_open > 0

    @pytest.mark.parametrize("annotate_on", [False, True],
                             ids=["both-off", "traceme-only"])
    def test_zero_with_the_recorder_off(self, annotate_on):
        tracing.disable()
        tracing.annotate(annotate_on)
        try:
            with tracing.span("prep") as sp:
                sp.next("step")
        finally:
            tracing.annotate(False)
        assert (sp is tracing.NO_SPAN) == (not annotate_on)
        assert (sp.t_open, sp.t_close) == (0, 0)

    @pytest.mark.parametrize("algo", ["windowed", "bucket"])
    def test_the_lane_leaves_them_on_its_ticket(self, recorder, algo):
        lim = create_limiter(_door_cfg(algo), backend="sketch",
                             clock=ManualClock(T0))
        try:
            t = lim.launch_hashed(np.arange(1, 17, dtype=np.uint64))
            lim.resolve(t)
            launch = [r for r in recorder.dump()
                      if r["stage"] in ("prep", "place", "step", "finish")]
            assert [r["stage"] for r in launch] == ["prep", "place", "step",
                                                    "finish"]
            assert t.t_lane == (launch[0]["t_start_ns"],
                                launch[-1]["t_end_ns"])
            tracing.disable()
            t = lim.launch_hashed(np.arange(1, 17, dtype=np.uint64))
            lim.resolve(t)
            assert t.t_lane == (0, 0)
        finally:
            lim.close()


class TestTraceparent:
    def test_parse_roundtrip(self):
        tid = tracing.new_trace_id()
        hdr = tracing.format_traceparent(tid)
        assert tracing.parse_traceparent(hdr) == tid

    def test_parse_garbage(self):
        assert tracing.parse_traceparent(None) == 0
        assert tracing.parse_traceparent("") == 0
        assert tracing.parse_traceparent("00-zz-yy-01") == 0
        assert tracing.parse_traceparent("nonsense") == 0


# ------------------------------------------------------------ wire framing


class TestWireTraceExtension:
    def test_with_trace_split_trace_roundtrip(self):
        frame = p.encode_allow_n(17, "user:1", 2)
        tid = tracing.new_trace_id()
        traced = p.with_trace(frame, tid)
        length, type_, req_id = p.parse_header(traced[:p.HEADER_SIZE])
        assert type_ == p.T_ALLOW_N | p.TRACE_FLAG and req_id == 17
        base, got_tid, body = p.split_trace(
            type_, traced[p.HEADER_SIZE:])
        assert base == p.T_ALLOW_N and got_tid == tid
        key, n = p.parse_allow_n(body)
        assert key == "user:1" and n == 2

    def test_untraced_passthrough(self):
        frame = p.encode_allow_n(1, "k", 1)
        _, type_, _ = p.parse_header(frame[:p.HEADER_SIZE])
        base, tid, body = p.split_trace(type_, frame[p.HEADER_SIZE:])
        assert base == p.T_ALLOW_N and tid == 0
        assert body == frame[p.HEADER_SIZE:]

    def test_response_types_cannot_carry_trace(self):
        ok = p.encode_ok(1)
        with pytest.raises(p.ProtocolError):
            p.with_trace(ok, 5)

    def test_traced_dcn_push_keeps_cap_and_hmac(self):
        # The trace prefix rides OUTSIDE the HMAC envelope: verification
        # is unchanged and the DCN size cap still applies to the base
        # type.
        delta = np.ones((2, 4), dtype=np.int64)
        frame = p.encode_dcn_debt(3, delta, secret="s3", sender=9,
                                  seq=123)
        traced = p.with_trace(frame, 77)
        length, type_, _ = p.parse_header(traced[:p.HEADER_SIZE],
                                          allow_dcn=True)
        base, tid, body = p.split_trace(type_, traced[p.HEADER_SIZE:])
        assert base == p.T_DCN_PUSH and tid == 77
        payload = p.unwrap_dcn_auth(body, "s3")
        kind, got, _ = p.parse_dcn(payload, 2, 4, 0)
        assert kind == p.DCN_KIND_DEBT
        np.testing.assert_array_equal(got, delta)


# ----------------------------------------------------- span-tree oracles


def _assert_span_tree(spans, tid, *, want_stages, n_slices=None):
    """The completeness oracle: every wanted stage present under the
    trace id, timestamps monotone (t_end >= t_start), same-stage spans
    non-overlapping per thread, and per-slice spans (when present)
    contained in the frame's device window."""
    mine = [s for s in spans if s["trace_id"] == tid]
    stages = {s["stage"] for s in mine}
    missing = set(want_stages) - stages
    assert not missing, f"stages missing from the trace: {missing}"
    for s in mine:
        assert s["t_end_ns"] >= s["t_start_ns"], s
    # Same-stage spans must not overlap within one thread (each thread's
    # pipeline processes one frame's stage at a time).
    by = {}
    for s in mine:
        by.setdefault((s["thread"], s["stage"]), []).append(s)
    for (_, stage), group in by.items():
        group.sort(key=lambda s: s["t_start_ns"])
        for a, b in zip(group, group[1:]):
            assert a["t_end_ns"] <= b["t_start_ns"], (
                f"overlapping {stage} spans in one thread")
    if n_slices is not None:
        slices = [s for s in mine if s["stage"] == "slice"]
        assert len({s["shard"] for s in slices}) == n_slices
        device = [s for s in mine if s["stage"] == "device"]
        assert device, "no device span to parent the slices"
        lo = min(d["t_start_ns"] for d in device)
        hi = max(d["t_end_ns"] for d in device)
        for s in slices:
            assert lo <= s["t_start_ns"] and s["t_end_ns"] <= hi, (
                "slice span escapes the frame's device window")


class TestAsyncioDoorSpanTree:
    def test_mixed_mesh_frame_traced_end_to_end(self, recorder):
        """One mixed frame through the asyncio door on a 2-slice mesh:
        client span -> io -> coalesce/queue/launch -> device -> barrier +
        per-slice spans -> resolve -> encode, all under ONE wire-
        propagated trace id."""
        cfg = _sketch_cfg()
        mesh = SlicedMeshLimiter(cfg, n_devices=2)

        async def run():
            srv = RateLimitServer(mesh, max_batch=4096, max_delay=200e-6)
            await srv.start()
            c = await AsyncClient.connect(srv.host, srv.port)
            tid = tracing.new_trace_id()
            # Raw ids chosen to fan out over BOTH slices (uniform ids
            # split ~evenly under splitmix64 % 2).
            ids = np.arange(1, 257, dtype=np.uint64)
            t0 = tracing.now()
            out = await c.allow_hashed(ids, trace_id=tid)
            tracing.record("client", t0, tracing.now(), trace_id=tid,
                           batch=len(out))
            assert len(out) == 256 and out.allowed.all()
            await c.close()
            await srv.shutdown()
            return tid

        tid = asyncio.run(run())
        spans = recorder.dump()
        _assert_span_tree(
            spans, tid,
            want_stages=("client", "io", "coalesce", "queue", "launch",
                         "device", "barrier", "slice", "resolve",
                         "encode"),
            n_slices=2)
        # The client span must enclose the whole server-side pipeline.
        mine = [s for s in spans if s["trace_id"] == tid]
        client = next(s for s in mine if s["stage"] == "client")
        for s in mine:
            if s["stage"] != "client":
                assert client["t_start_ns"] <= s["t_start_ns"]
                assert s["t_end_ns"] <= client["t_end_ns"]
        mesh.close()

    def test_string_lane_traced(self, recorder):
        lim = create_limiter(_sketch_cfg(), backend="sketch")

        async def run():
            srv = RateLimitServer(lim, max_batch=64, max_delay=200e-6)
            await srv.start()
            c = await AsyncClient.connect(srv.host, srv.port)
            tid = tracing.new_trace_id()
            res = await c.allow_n("user:1", 1, trace_id=tid)
            assert res.allowed
            await c.close()
            await srv.shutdown()
            return tid

        tid = asyncio.run(run())
        _assert_span_tree(recorder.dump(), tid,
                          want_stages=("io", "coalesce", "launch",
                                       "device", "resolve", "encode"))
        lim.close()


@pytest.mark.skipif(not native_server_available(),
                    reason="needs g++ for the native server")
class TestNativeDoorSpanTree:
    def test_mixed_mesh_frame_traced_end_to_end(self, recorder):
        """One mixed hashed frame through the NATIVE door with the mesh
        slices mounted as dispatch shards (1 shard == 1 device,
        ADR-012): the ABI 9 spans callback yields io -> dispatch ->
        device -> complete per touched shard, under the wire trace id."""
        from ratelimiter_tpu.parallel.limiter import build_slices

        slices = build_slices(_sketch_cfg(), n_devices=2)
        srv = NativeRateLimitServer(slices[0], "127.0.0.1", 0,
                                    max_batch=4096, max_delay=200e-6,
                                    shard_limiters=list(slices))
        srv.start()
        try:
            with Client(port=srv.port) as c:
                tid = tracing.new_trace_id()
                t0 = tracing.now()
                out = c.allow_hashed(np.arange(1, 257, dtype=np.uint64),
                                     trace_id=tid)
                tracing.record("client", t0, tracing.now(), trace_id=tid,
                               batch=len(out))
                assert len(out) == 256 and out.allowed.all()
                # stats() surfaces the cumulative per-stage aggregates
                # (ABI 9).
                st = srv.stats()
                assert st["stage_ns"]["batches"] > 0
                assert st["stage_ns"]["device"] > 0
        finally:
            srv.shutdown()
        spans = recorder.dump()
        _assert_span_tree(spans, tid,
                          want_stages=("client", "io", "dispatch",
                                       "device", "complete"))
        # Both shards (= devices) dispatched under this trace id.
        mine = [s for s in spans if s["trace_id"] == tid]
        assert {s["shard"] for s in mine
                if s["stage"] == "device"} == {0, 1}
        client = next(s for s in mine if s["stage"] == "client")
        for s in mine:
            if s["stage"] != "client":
                assert client["t_start_ns"] <= s["t_start_ns"]
                assert s["t_end_ns"] <= client["t_end_ns"]

    def test_string_lane_traced(self, recorder):
        lim = create_limiter(_sketch_cfg(), backend="sketch")
        srv = NativeRateLimitServer(lim, "127.0.0.1", 0, max_batch=64,
                                    max_delay=200e-6)
        srv.start()
        try:
            with Client(port=srv.port) as c:
                tid = tracing.new_trace_id()
                res = c.allow_n("user:1", 1, trace_id=tid)
                assert res.allowed
                res2 = c.allow_batch(["a", "b"], [1, 1], trace_id=tid)
                assert all(r.allowed for r in res2)
        finally:
            srv.shutdown()
        lim.close()
        _assert_span_tree(recorder.dump(), tid,
                          want_stages=("io", "dispatch", "device",
                                       "complete"))


# ------------------------------ the dispatch stage from inside (ISSUE 25)

_ALGOS = {"windowed": Algorithm.SLIDING_WINDOW,
          "bucket": Algorithm.TOKEN_BUCKET}
#: Both device programs x both lanes of the native door: the cases every
#: test of this section runs.
DOOR_CASES = [pytest.param(a, lane, id=f"{a}-{lane}")
              for a in _ALGOS for lane in ("hashed", "string")]
_SUB_STAGES = {"hashed": ("enter", "descend", "prep", "place", "step",
                          "finish", "ascend", "leave"),
               "string": ("enter", "hash", "descend", "prep", "place",
                          "step", "finish", "ascend", "leave")}


def _door_cfg(algo: str) -> Config:
    return Config(algorithm=_ALGOS[algo], limit=100, window=60.0,
                  sketch=SketchParams(depth=2, width=2048, sub_windows=8))


@contextlib.contextmanager
def _native_door(algo: str, registry=None, **door_kw):
    """A one-shard native door on a frozen clock (so that two runs of the
    same frames decide the same) with a connected client."""
    lim = create_limiter(_door_cfg(algo), backend="sketch",
                         clock=ManualClock(T0))
    srv = NativeRateLimitServer(lim, "127.0.0.1", 0, max_batch=4096,
                                max_delay=200e-6,
                                registry=registry or m.Registry(),
                                **door_kw)
    srv.start()
    try:
        with Client(port=srv.port) as c:
            yield srv, c
    finally:
        srv.shutdown()
        lim.close()


def _frame(c, lane: str, i: int, trace_id: int = 0) -> np.ndarray:
    """Frame i of the section's traffic -> its allow mask. Sixteen keys
    a frame at cost 30, four key sets in turn: a key crosses its limit of
    100 on its fourth frame."""
    lo = 16 * (i % 4)
    if lane == "hashed":
        out = c.allow_hashed(np.arange(lo + 1, lo + 17, dtype=np.uint64),
                             np.full(16, 30, dtype=np.int64),
                             trace_id=trace_id)
        return np.asarray(out.allowed)
    res = c.allow_batch([f"u:{j}" for j in range(lo, lo + 16)], [30] * 16,
                        trace_id=trace_id)
    return np.array([r.allowed for r in res])


@pytest.mark.skipif(not native_server_available(),
                    reason="needs g++ for the native server")
class TestDispatchStageFromInside:
    @pytest.mark.parametrize("algo,lane", DOOR_CASES)
    def test_one_ring_per_recording_thread(self, recorder, algo, lane):
        """The C++ dispatcher and completer enter Python through
        PyGILState pairs that make and destroy a thread state each: a
        ring kept in a threading.local was a ring per dispatch. 1,000
        dispatches = 1,000 _spans calls from the (foreign) completer."""
        with _native_door(algo) as (srv, c):
            for i in range(100):
                _frame(c, lane, i)
            after_100 = sorted(recorder._rings)
            for i in range(100, 1000):
                _frame(c, lane, i)
            after_1000 = sorted(recorder._rings)
            assert srv.stats()["stage_ns"]["batches"] == 1000
        # One shard: its dispatcher and its completer record, nobody else.
        assert 1 <= len(after_100) <= 2
        assert after_1000 == after_100
        assert all(r.name == f"native-{tid}"
                   for tid, r in recorder._rings.items())
        dispatches = [s for s in recorder.dump() if s["stage"] == "dispatch"]
        # Ring capacity 1024, 10 rows a dispatch on the completer's ring
        # (its eight door stages — io, dispatch, enter, descend, ascend,
        # leave, device, complete — and the resolve's "fetch", "unpack").
        assert len(dispatches) >= 102

    @pytest.mark.parametrize("algo,lane", DOOR_CASES)
    def test_sub_stages_tile_the_dispatch_span(self, recorder, algo, lane):
        n = 40
        with _native_door(algo) as (_, c):
            for i in range(n):
                _frame(c, lane, i, trace_id=i + 1)
        by_trace = {}
        for s in recorder.dump():
            by_trace.setdefault(s["trace_id"], {}).setdefault(
                s["stage"], []).append(s)
        covered, handed = [], []
        for tid in range(1, n + 1):
            mine = by_trace[tid]
            (whole,) = mine["dispatch"]
            at = whole["t_start_ns"]
            assert mine["enter"][0]["t_start_ns"] == at
            for stage in _SUB_STAGES[lane]:
                (sub,) = mine[stage]         # exactly one per dispatch
                assert sub["shard"] == 0 and sub["batch"] == 16
                assert at <= sub["t_start_ns"] <= sub["t_end_ns"], stage
                at = sub["t_end_ns"]         # in order, no overlap
            assert at == whole["t_end_ns"]    # leave ends where it does
            if lane == "hashed":
                assert "hash" not in mine
            inside = sum(mine[st][0]["t_end_ns"] - mine[st][0]["t_start_ns"]
                         for st in _SUB_STAGES[lane])
            covered.append(inside / (whole["t_end_ns"] - whole["t_start_ns"]))
            # The callback is tiled at ONE clock read a boundary:
            # "descend" runs from where "enter" ends (or the string
            # lane's "hash" closed) to the lane's first stamp, "ascend"
            # from its last to where "leave" begins — the ticket carries
            # the stamps (t_door, t_lane), _spans records the rows. So
            # does the resolve's "fetch" hand over to "unpack".
            order = _SUB_STAGES[lane]
            for a, b in zip(order, order[1:]):
                gap = mine[b][0]["t_start_ns"] - mine[a][0]["t_end_ns"]
                if (a, b) == ("enter", "hash"):
                    # The callback's entry stamp, then the span's own:
                    # two clock reads back to back.
                    assert gap >= 0
                    handed.append(gap)
                else:
                    assert gap == 0, (a, b, gap)
            (fetch,), (unpack,) = mine["fetch"], mine["unpack"]
            assert fetch["t_end_ns"] == unpack["t_start_ns"]
            assert unpack["t_end_ns"] >= unpack["t_start_ns"]
            (device,) = mine["device"]
            assert device["t_start_ns"] <= fetch["t_start_ns"] \
                and unpack["t_end_ns"] <= device["t_end_ns"]
        # Nothing is left between the sub-stages but, on the string
        # lane, the two clock reads between "enter" and "hash".
        if lane == "hashed":
            assert min(covered) == 1.0, covered
        else:
            assert statistics.median(covered) >= 0.97, covered
            assert statistics.median(handed) < 50_000, handed

    @pytest.mark.parametrize("algo,lane", DOOR_CASES)
    def test_decisions_identical_whatever_is_on(self, algo, lane):
        def run(recorder_on: bool, annotate_on: bool) -> np.ndarray:
            tracing.disable()
            tracing.annotate(annotate_on)
            if recorder_on:
                tracing.enable(1024)
            try:
                with _native_door(algo) as (_, c):
                    return np.concatenate(
                        [_frame(c, lane, i, trace_id=i + 1)
                         for i in range(16)])
            finally:
                tracing.disable()
                tracing.annotate(False)

        off = run(False, False)
        assert off.any() and not off.all()   # the limit was crossed
        np.testing.assert_array_equal(off, run(True, False))
        np.testing.assert_array_equal(off, run(True, True))
        # --trace alone: spans with a TraceMe and no row, stamps of 0.
        np.testing.assert_array_equal(off, run(False, True))

    @pytest.mark.parametrize("python_tracer", [False, True],
                             ids=["tracemes-only", "python-tracer"])
    @pytest.mark.parametrize("algo", list(_ALGOS))
    def test_spans_reach_the_profiler_timeline(self, tmp_path, algo,
                                               python_tracer):
        """--trace on (a TracingDecorator in the stack), a REAL capture
        through tracing.profile: the sub-stage spans are TraceMes of the
        profiler's own timeline, nested in the decorator's launch
        annotation; both clock anchors are among them with the
        recorder's clock as their argument, the second after the first;
        and the capture holds a Python-tracer frame (a name that starts
        with ``$``) only when it was asked for."""
        import jax.profiler

        from ratelimiter_tpu.observability.decorators import TracingDecorator

        tracing.annotate(False)
        lim = TracingDecorator(create_limiter(
            _door_cfg(algo), backend="sketch", clock=ManualClock(T0)))
        assert tracing.ANNOTATE                   # the decorator IS --trace
        ids = np.arange(1, 17, dtype=np.uint64)
        lim.resolve(lim.launch_ids(ids))          # compile outside the trace
        lim.resolve(lim.launch_hashed(ids))
        try:
            before = tracing.now()
            with tracing.profile(str(tmp_path),
                                 python_tracer=python_tracer) as anchors:
                after = tracing.now()
                assert anchors.end is None        # taken at the stop
                lim.resolve(lim.launch_hashed(ids))
                lim.resolve(lim.launch_ids(ids, wire=True))
        finally:
            tracing.annotate(False)
            lim.close()
        assert before <= anchors.start <= after <= anchors.end
        (pb,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        names = [e.name
                 for plane in jax.profiler.ProfileData.from_file(pb).planes
                 for line in plane.lines for e in line.events]
        frames = [n for n in names if n.startswith("$")]
        assert bool(frames) == python_tracer, frames[:5]
        found = [e for plane in jax.profiler.ProfileData.from_file(pb).planes
                 for line in plane.lines for e in line.events
                 if e.name.startswith("ratelimiter/")]
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in found]
        mark, mark_end = sorted(
            (e for e in found if e.name == "ratelimiter/clock_anchor"),
            key=lambda e: e.start_ns)
        assert dict(mark.stats)["mono_ns"] == anchors.start
        assert dict(mark_end.stats)["mono_ns"] == anchors.end
        # One offset lays CLOCK_MONOTONIC on the profile; the second
        # anchor bounds its drift over the capture.
        drift = (mark_end.start_ns - mark.start_ns) \
            - (anchors.end - anchors.start)
        assert abs(drift) < 5_000_000, drift
        launches = [e for e in events
                    if e[0] == f"ratelimiter/{_ALGOS[algo].value}/launch"]
        assert len(launches) == 2
        for _, lo, hi in launches:
            assert mark.start_ns <= lo <= hi <= mark_end.start_ns
            inside = [name for name, a, b in sorted(events, key=lambda e: e[1])
                      if lo <= a and b <= hi and name.count("/") == 1]
            assert inside == ["ratelimiter/prep", "ratelimiter/place",
                              "ratelimiter/step", "ratelimiter/finish"]

    @pytest.mark.parametrize("door", ["capture", "debug_profile"])
    def test_one_profiler_start_stop(self, monkeypatch, tmp_path, door):
        """TracingDecorator.capture and /debug/profile share
        tracing.profile: one start, one stop, the anchor in between —
        and the endpoint's reply names the anchor's reading."""
        import jax.profiler

        from ratelimiter_tpu.observability.decorators import TracingDecorator

        calls = []
        levels = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, profiler_options=None: (
                calls.append(("start", d)),
                levels.append(profiler_options.python_tracer_level)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop", None)))
        readings = iter((1234567, 7654321))
        monkeypatch.setattr(
            tracing, "clock_anchor",
            lambda: calls.append(("anchor", None)) or next(readings))
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        try:
            if door == "capture":
                with TracingDecorator(lim).capture(str(tmp_path)):
                    calls.append(("body", None))
                assert calls[0] == ("start", str(tmp_path))
            else:
                gw = HttpGateway(lambda key, n: lim.allow_n(key, n),
                                 lim.reset, enable_debug=True)
                gw.start()
                try:
                    code, body = TestDebugEndpoints()._get(
                        gw.port, "/debug/profile?seconds=0.05")
                finally:
                    gw.shutdown()
                assert code == 200, body
                assert body["clock_anchor_mono_ns"] == 1234567
                assert body["clock_anchor_end_mono_ns"] == 7654321
                assert body["python_tracer"] is False
                calls.insert(2, ("body", None))
        finally:
            tracing.annotate(False)
            lim.close()
        assert [c[0] for c in calls] == ["start", "anchor", "body", "anchor",
                                         "stop"]
        assert levels == [0]            # no Python tracer unless asked

    def test_debug_profile_python_switch(self, monkeypatch):
        """``/debug/profile?python=1`` is the one way back to the old
        capture (the profiler's Python tracer on)."""
        import jax.profiler

        levels = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, profiler_options=None: levels.append(
                profiler_options.python_tracer_level))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        gw = HttpGateway(lambda key, n: lim.allow_n(key, n), lim.reset,
                         enable_debug=True)
        gw.start()
        try:
            for query, want in (("", False), ("&python=0", False),
                                ("&python=1", True)):
                code, body = TestDebugEndpoints()._get(
                    gw.port, "/debug/profile?seconds=0.01" + query)
                assert code == 200 and body["python_tracer"] is want
        finally:
            gw.shutdown()
            lim.close()
        assert levels == [0, 0, 1]

    @pytest.mark.parametrize("algo,lane", DOOR_CASES)
    def test_door_stage_counters_on_metrics(self, algo, lane):
        """The door's always-on stage sums: exported at scrape time, with
        the recorder off."""
        tracing.disable()
        reg = m.Registry()
        with _native_door(algo, registry=reg) as (srv, c):
            for i in range(10):
                _frame(c, lane, i)
            text = c.metrics()
            door_after = srv.stats()
            door = door_after["stage_ns"]
        samples = {}
        for line in text.splitlines():
            if line.startswith("rate_limiter_door_"):
                name, value = line.rsplit(" ", 1)
                samples[name] = float(value)
        assert samples["rate_limiter_door_dispatches_total"] \
            == door["batches"] == 10
        for stage in ("io", "dispatch", "device", "complete"):
            got = samples[
                f'rate_limiter_door_stage_seconds_total{{stage="{stage}"}}']
            # The scrape came before stats(): sums only grow in between
            # (the exposition keeps six significant digits).
            assert 0 < got <= door[stage] / 1e9 * (1 + 1e-5), stage
        assert "rate_limiter_stage_seconds" not in text   # recorder off
        # The thread books, every (thread, state) of the table and the
        # four roles' CPU clocks, with nothing switched on.
        for thread, states in _THREAD_STATES.items():
            for state in states:
                got = samples["rate_limiter_door_thread_seconds_total"
                              f'{{state="{state}",thread="{thread}"}}']
                assert 0 <= got <= (door_after["thread_ns"][thread][state]
                                    / 1e9 * (1 + 1e-5)), (thread, state)
            assert set(door_after["thread_ns"][thread]) == set(states)
        for thread in ("io", "dispatcher", "completer", "responder"):
            got = samples["rate_limiter_door_thread_cpu_seconds_total"
                          f'{{thread="{thread}"}}']
            assert 0 < got <= (door_after["thread_cpu_ns"][thread]
                               / 1e9 * (1 + 1e-5)), thread


# ------------------------------------------- the threads themselves (37)

_THREAD_STATES = {
    "dispatcher": ("idle", "gather", "gil", "python", "slot", "other"),
    "completer": ("idle", "gil", "python", "other"),
}


def _books(srv) -> tuple:
    """(stats(), the instants just before and after the call)."""
    before = tracing.now()
    st = srv.stats()
    return st, before, tracing.now()


@pytest.mark.skipif(not native_server_available(),
                    reason="needs g++ for the native server")
class TestThreadBooks:
    """rate_limiter_door_thread_seconds_total's source, stats()
    ["thread_ns"]: C++ atomics added to where a thread changes state,
    recorder OFF throughout."""

    @pytest.mark.parametrize("algo,lane", DOOR_CASES)
    def test_states_tile_each_threads_wall(self, algo, lane):
        tracing.disable()
        with _native_door(algo) as (srv, c):
            _frame(c, lane, 0)                    # the step is compiled
            reads = [_books(srv)]
            for k in range(4):
                for i in range(250):
                    _frame(c, lane, 1 + 250 * k + i)
                reads.append(_books(srv))
            assert srv.stats()["stage_ns"]["batches"] == 1001
        (first, _, t0_hi), (last, t1_lo, _) = reads[0], reads[-1]
        (_, t0_lo, _), (_, _, t1_hi) = reads[0], reads[-1]
        for thread, states in _THREAD_STATES.items():
            # Monotone, state by state, over five readings.
            for (a, _, _), (b, _, _) in zip(reads, reads[1:]):
                for state in states:
                    assert a["thread_ns"][thread][state] \
                        <= b["thread_ns"][thread][state], (thread, state)
            spent = sum(last["thread_ns"][thread][st]
                        - first["thread_ns"][thread][st] for st in states)
            # The states' sum IS the wall between the two reads: exact
            # to the width of the two stats() calls (2 % asked).
            assert t1_lo - t0_hi <= spent <= t1_hi - t0_lo, thread
            assert abs(spent / (t1_hi - t0_lo) - 1) < 0.02, thread
            # The thread computed: CPU > 0 and no more than its wall.
            cpu = last["thread_cpu_ns"][thread] \
                - first["thread_cpu_ns"][thread]
            assert 0 < cpu <= spent, (thread, cpu, spent)
            # 1,000 launches / resolves were made inside `python`.
            grown = {st: last["thread_ns"][thread][st]
                     - first["thread_ns"][thread][st] for st in states}
            assert grown["python"] > 0 and grown["idle"] > 0, grown
        assert last["thread_ns"]["dispatcher"]["gather"] \
            > first["thread_ns"]["dispatcher"]["gather"]
        wall = t1_hi - t0_lo
        io_threads = last["net"]["rings"]
        for thread, n in (("io", io_threads), ("responder", 1)):
            cpu = last["thread_cpu_ns"][thread] \
                - first["thread_cpu_ns"][thread]
            assert 0 < cpu <= n * wall, (thread, cpu)

    @pytest.mark.parametrize("lane", ["hashed", "string"])
    def test_the_blocking_path_feeds_the_same_states(self, lane):
        """--inflight 1 is the blocking decide (no window, no completer
        thread): the dispatcher's book is kept the same way, through the
        same GilHold, and the completer's stays empty."""
        tracing.disable()
        with _native_door("windowed", inflight=1) as (srv, c):
            _frame(c, lane, 0)
            first, _, t0_hi = _books(srv)
            for i in range(100):
                _frame(c, lane, 1 + i)
            last, t1_lo, _ = _books(srv)
            assert last["pipelined"] is False
        grown = {st: last["thread_ns"]["dispatcher"][st]
                 - first["thread_ns"]["dispatcher"][st]
                 for st in _THREAD_STATES["dispatcher"]}
        assert sum(grown.values()) >= t1_lo - t0_hi
        assert grown["python"] > 0 and grown["gather"] > 0 \
            and grown["idle"] > 0 and grown["slot"] == 0
        assert set(last["thread_ns"]["completer"].values()) == {0}
        assert last["thread_cpu_ns"]["completer"] == 0

    def test_a_reading_is_consistent_while_the_threads_run(self):
        """stats() reads a book its owner is writing (sums, then the
        packed stamp-and-state word, re-read): every reading's states sum
        to that thread's wall since it started — a segment dropped or
        counted twice at a transition would break it — with a reader
        hammering stats() while frames flow."""
        tracing.disable()
        readings = []
        done = threading.Event()
        with _native_door("windowed") as (srv, c):
            _frame(c, "hashed", 0)

            def hammer():
                while not done.is_set():
                    readings.append(_books(srv))

            reader = threading.Thread(target=hammer, daemon=True)
            reader.start()
            try:
                for i in range(300):
                    _frame(c, "hashed", 1 + i)
            finally:
                done.set()
                reader.join(timeout=30)
            assert not reader.is_alive()
        assert len(readings) > 50
        (base, base_lo, base_hi) = readings[0]
        for st, lo, hi in readings[1:]:
            for thread, states in _THREAD_STATES.items():
                grown = sum(st["thread_ns"][thread][k]
                            - base["thread_ns"][thread][k] for k in states)
                assert lo - base_hi <= grown <= hi - base_lo, thread

    @pytest.mark.parametrize("lane", ["hashed", "string"])
    def test_the_door_reads_no_span_clock_with_tracing_off(self, lane,
                                                           monkeypatch):
        """Both sinks off: the launch callback and the lane read no
        clock the parent's did not (the callback's one perf_counter for
        the launch histogram)."""
        tracing.disable()
        tracing.annotate(False)
        reads = []
        real = tracing.now
        monkeypatch.setattr(tracing, "now",
                            lambda: reads.append(1) or real())
        assert tracing.span("prep") is tracing.NO_SPAN
        with _native_door("windowed") as (srv, c):
            masks = [_frame(c, lane, i) for i in range(8)]
            assert srv.stats()["stage_ns"]["batches"] == 8
        assert all(mask.shape == (16,) for mask in masks)
        assert not reads

    @staticmethod
    def _frames_under_a_spinner(frame, n: int, gil_ns) -> tuple:
        """n frames while a Python thread that never yields spins;
        (gil_ns() after them, the spinner's loop count)."""
        stop = threading.Event()
        spun = [0]

        def spin():
            while not stop.is_set():
                spun[0] += 1

        spinner = threading.Thread(target=spin, daemon=True)
        spinner.start()
        try:
            for i in range(n):
                frame(i)
        finally:
            stop.set()
            spinner.join(timeout=30)
        assert not spinner.is_alive() and spun[0] > 0
        return (*gil_ns(), spun[0])

    @pytest.mark.parametrize("lane", ["hashed", "string"])
    def test_gil_wait_is_counted_where_it_happens(self, lane):
        """A Python thread that never yields the interpreter gives it up
        only when asked, a switch interval after the asking: under it
        the dispatcher's `gil` grows by milliseconds over forty
        dispatches (a whole interval a dispatch on a quiet machine; a
        fraction of one here, where the client's and the completer's
        requests force hand-overs the dispatcher rides on), and without
        it by microseconds. Only the two readings' proportion is held to
        — this runs beside five other test processes, and a descheduled
        thread reads as a wait: the best of three attempts."""
        tracing.disable()
        n = 40
        seen = []
        with _native_door("windowed") as (srv, c):
            _frame(c, lane, 0)

            def gil_ns():
                return (srv.stats()["thread_ns"]["dispatcher"]["gil"],)

            for attempt in range(3):
                at = 1 + 2 * n * attempt
                (d0,) = gil_ns()
                for i in range(n):
                    _frame(c, lane, at + i)
                (d1,) = gil_ns()
                d2, spun = self._frames_under_a_spinner(
                    lambda i: _frame(c, lane, at + n + i), n, gil_ns)
                quiet, held = d1 - d0, d2 - d1
                seen.append((quiet, held, spun))
                # Measured: quiet ~5 us a dispatch; held 0.7-6 ms.
                if held >= 10 * max(quiet, n * 10_000):
                    break
            else:
                pytest.fail(f"(quiet, held, spun) ns over {n} dispatches "
                            f"a reading: {seen}")

    @pytest.mark.parametrize("inflight,waits", [(2, True), (8, False)],
                             ids=["window-of-2", "window-of-8"])
    def test_slot_wait_only_when_the_window_is_full(self, inflight, waits):
        """`slot` is the wait in cv_space.wait alone: four frames in
        flight against a resolve slowed to 10 ms fill a window of two
        (the smallest a pipelined door has: --inflight 1 is the blocking
        path, which has no window) and never a window of eight."""
        tracing.disable()
        frames, conns = 10, 4
        with _native_door("windowed", inflight=inflight) as (srv, c):
            _frame(c, "hashed", 0)
            lim = srv._shard_limiters[0]
            plain = lim.resolve

            def slow_resolve(ticket):
                time.sleep(0.01)
                return plain(ticket)

            lim.resolve = slow_resolve
            before = srv.stats()["thread_ns"]["dispatcher"]["slot"]

            def client(k):
                with Client(port=srv.port) as mine:
                    for i in range(frames):
                        _frame(mine, "hashed", 1 + k * frames + i)

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(conns)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            slot = srv.stats()["thread_ns"]["dispatcher"]["slot"] - before
            assert srv.stats()["stage_ns"]["batches"] >= 1 + frames
        if waits:
            assert slot > 50_000_000, slot       # ~10 ms a dispatch
        else:
            assert slot == 0


# --------------------------------------------------- zero-overhead smoke


class TestZeroOverhead:
    def test_decisions_identical_recorder_on_vs_off(self):
        """Tracing must never change behavior: same traffic, recorder on
        vs off, byte-identical decision stream."""
        def run(enable: bool):
            tracing.disable()
            if enable:
                tracing.enable(1024)
            try:
                lim = create_limiter(
                    _sketch_cfg(), backend="sketch",
                    clock=ManualClock(T0))

                async def drive():
                    srv = RateLimitServer(lim, max_batch=32,
                                          max_delay=100e-6)
                    await srv.start()
                    c = await AsyncClient.connect(srv.host, srv.port)
                    out = []
                    ids = np.arange(1, 65, dtype=np.uint64)
                    for i in range(8):
                        br = await c.allow_hashed(
                            ids, trace_id=(i + 1) if enable else 0)
                        out.append(br.allowed.copy())
                        rs = await c.allow_batch(
                            [f"u:{j}" for j in range(16)],
                            trace_id=(i + 1) if enable else 0)
                        out.append(np.array([r.allowed for r in rs]))
                    await c.close()
                    await srv.shutdown()
                    return np.concatenate(out)

                got = asyncio.run(drive())
                lim.close()
                return got
            finally:
                tracing.disable()

        off = run(False)
        on = run(True)
        np.testing.assert_array_equal(off, on)

    def test_recorder_on_throughput_smoke(self):
        """Pinned throughput smoke for the acceptance bar (recorder ON
        within 3% of OFF on the standard bench). The claim guarded here
        is structural — spans are stamped per *dispatch*, never per
        decision, at clock-read cost — so the CI margin is loose (1.5x)
        to absorb shared-runner scheduler noise; the tight A/B is a
        chip measurement (PERF.md §6, PR 25 and PR 37). Off and on
        rounds alternate on one warm limiter and their MEDIANS are
        compared: a stall of the box under
        six test workers lands in one round of either side, not in a
        whole side as it did when off ran to its end before on began."""
        import time as _time

        from ratelimiter_tpu.serving.batcher import MicroBatcher

        lim = create_limiter(_sketch_cfg(), backend="sketch")
        ids = np.arange(1, 2049, dtype=np.uint64)
        ns = np.ones(len(ids), dtype=np.int64)

        def one_round(enable: bool) -> float:
            tracing.disable()
            if enable:
                tracing.enable(4096)

            async def drive() -> float:
                b = MicroBatcher(lim, max_batch=4096, max_delay=50e-6,
                                 registry=m.Registry())
                await b.submit_hashed_nowait(ids, ns)   # warm/compile
                t0 = _time.perf_counter()
                for i in range(20):
                    await b.submit_hashed_nowait(
                        ids, ns, trace_id=(i + 1) if enable else 0)
                dt = _time.perf_counter() - t0
                await b.drain()
                b.close()
                return dt

            try:
                return asyncio.run(drive())
            finally:
                tracing.disable()

        try:
            one_round(False)                            # compile, untimed
            rounds = {False: [], True: []}
            for i in range(14):
                rounds[bool(i % 2)].append(one_round(bool(i % 2)))
        finally:
            lim.close()
        off = statistics.median(rounds[False])
        on = statistics.median(rounds[True])
        assert on <= off * 1.5, (
            f"recorder-on hot path regressed: median {on:.4f}s vs "
            f"{off:.4f}s for 20 traced 2048-id dispatches "
            f"(on {rounds[True]}, off {rounds[False]})")

    def test_hot_path_defaults_off(self, monkeypatch):
        tracing.disable()
        tracing.annotate(False)
        assert tracing.RECORDER is None
        from ratelimiter_tpu.serving.batcher import MicroBatcher
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))

        async def drive():
            b = MicroBatcher(lim, max_batch=16, registry=m.Registry())
            fut = b.submit_nowait("k", 1)
            res = await fut
            await b.drain()
            b.close()
            return res

        res = asyncio.run(drive())
        assert res.allowed
        assert tracing.RECORDER is None
        # Both sinks off: span() is one shared object, and the launch
        # path under it reads no clock.
        assert tracing.span("prep", batch=4) is tracing.NO_SPAN
        monkeypatch.setattr(tracing, "now", lambda: pytest.fail(
            "the launch path read the span clock with tracing off"))
        out = lim.resolve(lim.launch_hashed(
            np.arange(1, 5, dtype=np.uint64), now=T0))
        assert out.allowed.all()
        lim.close()


# --------------------------------------------------- metrics satellites


class TestMetricsSatellites:
    def test_label_value_escaping(self):
        reg = m.Registry()
        c = reg.counter("t_total", "h")
        evil = 'a"b\\c\nd'
        c.inc(key=evil)
        text = reg.render()
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("t_total{"))
        assert line == 't_total{key="a\\"b\\\\c\\nd"} 1'
        # The exposition must stay one-sample-per-line: no raw newline
        # leaked into the body.
        assert 'a"b' not in text

    def test_histogram_bisect_matches_linear_reference(self):
        buckets = m.LATENCY_BUCKETS
        h = m.Histogram("h_seconds", "h", buckets)
        rng = np.random.default_rng(0)
        values = list(rng.uniform(0, 3.0, size=500))
        values += list(buckets)  # exact boundary values: `<=` semantics

        def linear_bucket(v):
            for i, ub in enumerate(buckets):
                if v <= ub:
                    return i
            return len(buckets)

        want = [0] * (len(buckets) + 1)
        for v in values:
            h.observe(v)
            want[linear_bucket(v)] += 1
        got = h._counts[()]
        assert got[:-1] == want[:-1] and got[-1] == want[-1]
        assert h.count() == len(values)
        assert h.sum() == pytest.approx(sum(values))

    def test_locked_reads_race_free(self):
        c = m.Counter("race_total", "h")
        g = m.Gauge("race_g", "h")
        h = m.Histogram("race_seconds", "h")
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                c.inc(key=f"k{i % 50}")
                g.inc(key=f"k{i % 50}")
                h.observe(0.01)
                i += 1

        t = threading.Thread(target=writer)
        t.start()
        try:
            for _ in range(2000):
                c.value(key="k1")
                g.value(key="k1")
                h.count()
                h.sum()
        finally:
            stop.set()
            t.join()

    def test_openmetrics_exemplars(self):
        reg = m.Registry()
        h = reg.histogram("lat_seconds", "h")
        h.observe(0.003, exemplar="00000000000000ab", stage="device")
        h.observe(0.004, stage="device")  # unsampled: no exemplar update
        # Past every bucket bound -> the +Inf overflow bucket keeps its
        # exemplar too (the slowest observations are the ones worth a
        # trace id).
        h.observe(99.0, exemplar="00000000000000cd", stage="device")
        classic = reg.render()
        assert "# {" not in classic       # classic text has no exemplars
        om = reg.render_openmetrics()
        assert '# {trace_id="00000000000000ab"} 0.003' in om
        assert '# {trace_id="00000000000000cd"} 99' in om
        inf_line = next(l for l in om.splitlines()
                        if 'le="+Inf"' in l and "lat_seconds" in l)
        assert "00000000000000cd" in inf_line
        assert om.rstrip().endswith("# EOF")

    def test_openmetrics_counter_family_name(self):
        """OpenMetrics counter families must be named WITHOUT the
        `_total` suffix in HELP/TYPE while the sample keeps it —
        `# TYPE x_total counter` fails Prometheus's strict OM parser
        and drops the whole scrape."""
        reg = m.Registry()
        c = reg.counter("req_total", "requests")
        c.inc(door="binary")
        classic = reg.render()
        assert "# TYPE req_total counter" in classic
        assert 'req_total{door="binary"} 1' in classic
        om = reg.render_openmetrics()
        assert "# TYPE req counter" in om
        assert "# TYPE req_total" not in om
        assert 'req_total{door="binary"} 1' in om

    def test_stage_histograms_via_collect_hook(self):
        reg = m.Registry()
        tracing.disable()
        rec = tracing.enable(256, registry=reg)
        try:
            t0 = tracing.now()
            rec.record("device", t0, t0 + 2_000_000, trace_id=0xAB)
            text = reg.render_openmetrics()
            assert "rate_limiter_stage_seconds" in text
            assert 'stage="device"' in text
            assert f'trace_id="{0xAB:016x}"' in text
            # Scrape again: the cursor advanced, counts must not double.
            text2 = reg.render()
            line = next(
                ln for ln in text2.splitlines()
                if ln.startswith("rate_limiter_stage_seconds_count"))
            assert line.endswith(" 1")
        finally:
            tracing.disable()


# ------------------------------------------------------- debug endpoints


class TestDebugEndpoints:
    def _get(self, port, path, token=None, timeout=10):
        req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
        if token:
            req.add_header("Authorization", f"Bearer {token}")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, json.loads(resp.read().decode())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read().decode())

    def test_debug_trace_gating_and_dump(self, recorder):
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        gw = HttpGateway(lambda key, n: lim.allow_n(key, n), lim.reset,
                         enable_debug=True, debug_token="s3cr3t")
        gw.start()
        try:
            t0 = tracing.now()
            recorder.record("device", t0, t0 + 1000, trace_id=5)
            code, _ = self._get(gw.port, "/debug/trace")
            assert code == 403                       # bearer required
            code, body = self._get(gw.port, "/debug/trace", token="s3cr3t")
            assert code == 200 and body["enabled"]
            assert any(ev["name"] == "device"
                       for ev in body["traceEvents"])
        finally:
            gw.shutdown()
            lim.close()

    def test_debug_profile_keeps_the_response_alive(self, monkeypatch):
        """Stopping a trace can outlast any client's socket timeout (a
        minute for 5 s of one busy chip): the 200 goes out when the
        capture has started, a byte follows every PROFILE_HEARTBEAT_S
        while it runs and is decoded, and the body is JSON after that
        whitespace. A read timeout shorter than the whole capture does
        not fire."""
        import json
        import time
        import urllib.request

        import jax.profiler

        from ratelimiter_tpu.serving import http_gateway

        monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: time.sleep(1.0))
        monkeypatch.setattr(http_gateway, "PROFILE_HEARTBEAT_S", 0.1)
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        gw = HttpGateway(lambda key, n: lim.allow_n(key, n), lim.reset,
                         enable_debug=True)
        gw.start()
        try:
            t0 = time.monotonic()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{gw.port}/debug/profile?seconds=0.3",
                    timeout=0.6) as resp:      # < 0.3 s capture + 1.0 s stop
                assert resp.status == 200
                raw = resp.read()
            assert time.monotonic() - t0 >= 1.3
            beats = len(raw) - len(raw.lstrip(b" "))
            assert 5 <= beats <= 14
            body = json.loads(raw)
            assert body["ok"] and body["seconds"] == 0.3
            # The capture's lock is free again.
            code, _ = self._get(gw.port, "/debug/profile?seconds=0.01")
            assert code == 200
        finally:
            gw.shutdown()
            lim.close()

    def test_debug_profile_start_failure_is_a_503(self, monkeypatch):
        import jax.profiler

        def boom(d, **kw):
            raise RuntimeError("no profiler here")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        gw = HttpGateway(lambda key, n: lim.allow_n(key, n), lim.reset,
                         enable_debug=True)
        gw.start()
        try:
            code, body = self._get(gw.port, "/debug/profile?seconds=0.01")
            assert code == 503 and "no profiler here" in body["error"]
            code, _ = self._get(gw.port, "/debug/profile?seconds=0.01")
            assert code == 503              # not 409: the lock was released
        finally:
            gw.shutdown()
            lim.close()

    def test_debug_disabled_by_default(self):
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        gw = HttpGateway(lambda key, n: lim.allow_n(key, n), lim.reset)
        gw.start()
        try:
            code, _ = self._get(gw.port, "/debug/trace")
            assert code == 403
            code, _ = self._get(gw.port, "/debug/profile?seconds=0.1")
            assert code == 403
        finally:
            gw.shutdown()
            lim.close()

    @pytest.mark.slow
    def test_debug_profile_capture(self, recorder):
        # Slow lane: the generous ceiling below is real — late in a
        # full-suite run this single test has been MEASURED at 120 s
        # (TSL profiler-server init), a seventh of the tier-1 budget.
        # The tracing CI lane runs it unfiltered in a fresh process,
        # where the init is seconds.
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        gw = HttpGateway(lambda key, n: lim.allow_n(key, n), lim.reset,
                         enable_debug=True)
        gw.start()
        try:
            # The process's FIRST capture pays several seconds of
            # profiler-server init on top of the capture window — and
            # late in a full-suite run (hundreds of live threads, a
            # loaded box) that init has been observed past 90 s, so the
            # ceiling is generous: this asserts the endpoint WORKS, not
            # how fast TSL brings up its profiler server.
            code, body = self._get(gw.port, "/debug/profile?seconds=0.2",
                                   timeout=300)
            # 503 = profiler unavailable on this platform (reported, not
            # crashed); 200 = capture artifacts on disk.
            assert code in (200, 503)
            if code == 200:
                assert body["ok"] and body["files"]
        finally:
            gw.shutdown()
            lim.close()

    def test_traceparent_reaches_trace_aware_decide(self, recorder):
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        seen = {}

        def decide(key, n, trace_id=0):
            seen["tid"] = trace_id
            return lim.allow_n(key, n)

        gw = HttpGateway(decide, lim.reset)
        gw.start()
        try:
            tid = tracing.new_trace_id()
            req = urllib.request.Request(
                f"http://127.0.0.1:{gw.port}/v1/allow?key=u1")
            req.add_header("traceparent", tracing.format_traceparent(tid))
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["traceparent"]
            assert seen["tid"] == tid
            spans = recorder.dump()
            assert any(s["stage"] == "http" and s["trace_id"] == tid
                       for s in spans)
        finally:
            gw.shutdown()
            lim.close()

    def test_metrics_openmetrics_negotiation(self, recorder):
        reg = m.Registry()
        h = reg.histogram("neg_seconds", "h")
        h.observe(0.001, exemplar="ff")
        lim = create_limiter(_sketch_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        gw = HttpGateway(lambda key, n: lim.allow_n(key, n), lim.reset,
                         metrics_render=reg.render)
        gw.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{gw.port}/metrics")
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert "# EOF" not in resp.read().decode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{gw.port}/metrics")
            req.add_header("Accept", "application/openmetrics-text")
            with urllib.request.urlopen(req, timeout=10) as resp:
                text = resp.read().decode()
                assert "openmetrics-text" in resp.headers["Content-Type"]
                assert text.rstrip().endswith("# EOF")
                assert 'trace_id="ff"' in text
        finally:
            gw.shutdown()
            lim.close()


# ------------------------------------- what a served dispatch leaves behind

#: The stage set of one dispatch of the native door, as PERF.md §3 names
#: it: the completer's ring holds the door's own rows (io, dispatch,
#: device, complete), the callback's tiling (enter, descend, ascend,
#: leave) and the resolve's (fetch, unpack); the dispatcher's ring the
#: lane's launch (prep, place, step, finish) and, on the string lane,
#: hash.
_SERVED_STAGES = {
    "hashed": {"io", "dispatch", "device", "complete", "enter", "descend",
               "ascend", "leave", "fetch", "unpack", "prep", "place",
               "step", "finish"},
}
_SERVED_STAGES["string"] = _SERVED_STAGES["hashed"] | {"hash"}


@pytest.mark.skipif(not native_server_available(),
                    reason="needs g++ for the native server")
class TestServedDispatchStageSet:
    @pytest.mark.parametrize("lane", ["hashed", "string"])
    def test_the_rings_hold_every_named_stage_and_nothing_else(
            self, recorder, lane):
        """Frames through the native door with the recorder on: every
        stage a per-layer reader looks for is in the rings, once a
        dispatch, and no row carries a name no reader knows (a renamed
        or added span would read as zero in the benchmark's breakdown
        without failing anything there)."""
        n = 12
        with _native_door("windowed") as (srv, c):
            for i in range(n):
                _frame(c, lane, i, trace_id=i + 1)
            assert srv.stats()["stage_ns"]["batches"] == n
        rows = {}
        for s in recorder.dump():
            rows.setdefault(s["stage"], []).append(s["trace_id"])
        assert set(rows) == _SERVED_STAGES[lane]
        for stage, tids in rows.items():
            assert sorted(tids) == list(range(1, n + 1)), stage


# ---------------------------------------- the client's trace sampling


class _Door:
    """Either front door over one sketch limiter, with a synchronous
    ``allow_hashed(ids, trace_id)`` whatever the door's client is."""

    #: What a traced hashed frame leaves on each door (ADR-014).
    STAGES = {"asyncio": ("client", "io", "coalesce", "launch", "device",
                          "resolve", "encode"),
              "native": ("client", "io", "dispatch", "device", "complete")}

    def __init__(self, door: str):
        self.door = door
        self.lim = create_limiter(_sketch_cfg(), backend="sketch")
        if door == "native":
            self.srv = NativeRateLimitServer(self.lim, "127.0.0.1", 0,
                                             max_batch=4096,
                                             max_delay=200e-6)
            self.srv.start()
            self.client = Client(port=self.srv.port)
        else:
            self.loop = asyncio.new_event_loop()
            self.srv = RateLimitServer(self.lim, max_batch=4096,
                                       max_delay=200e-6)
            self._run(self.srv.start())
            self.client = self._run(
                AsyncClient.connect(self.srv.host, self.srv.port))

    def _run(self, coro):
        return self.loop.run_until_complete(coro)

    def allow_hashed(self, ids, trace_id):
        if self.door == "native":
            return self.client.allow_hashed(ids, trace_id=trace_id)
        return self._run(self.client.allow_hashed(ids, trace_id=trace_id))

    def close(self):
        if self.door == "native":
            self.client.close()
            self.srv.shutdown()
        else:
            self._run(self.client.close())
            self._run(self.srv.shutdown())
            self.loop.close()
        self.lim.close()


class TestClientTraceSampling:
    @pytest.mark.parametrize("door", [
        "asyncio",
        pytest.param("native", marks=pytest.mark.skipif(
            not native_server_available(),
            reason="needs g++ for the native server"))])
    def test_one_frame_in_n_is_stamped_and_no_other(self, recorder, door):
        """A client that gives every Nth frame a fresh wire trace id
        (the loadgen's sampling, ADR-014): the server's rings hold a
        whole span tree under each sampled id, that tree is the sampled
        FRAME's (its row count), and every other frame's rows carry no
        id at all."""
        every, frames = 4, 16
        served = _Door(door)
        sampled = {}                         # trace id -> rows of its frame
        try:
            for i in range(frames):
                ids = np.arange(1, 9 + i, dtype=np.uint64)   # 8 + i rows
                tid = tracing.new_trace_id() if i % every == 0 else 0
                t0 = tracing.now()
                out = served.allow_hashed(ids, tid)
                assert len(out) == 8 + i
                if tid:
                    tracing.record("client", t0, tracing.now(),
                                   trace_id=tid, batch=len(out))
                    sampled[tid] = 8 + i
        finally:
            served.close()
        assert len(sampled) == frames // every
        spans = recorder.dump()
        assert {s["trace_id"] for s in spans if s["trace_id"]} \
            == set(sampled)
        for tid, rows in sampled.items():
            _assert_span_tree(spans, tid, want_stages=_Door.STAGES[door])
            mine = [s for s in spans if s["trace_id"] == tid]
            # One of each stage, each of this frame's size.
            assert len(mine) == len({s["stage"] for s in mine})
            assert {s["batch"] for s in mine} == {rows}
        # The frames in between were served and recorded, unstamped.
        device = [s for s in spans if s["stage"] == "device"]
        assert sorted(s["batch"] for s in device if not s["trace_id"]) \
            == [8 + i for i in range(frames) if i % every]
