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
  counters on ``/metrics``.
"""

from __future__ import annotations

import asyncio
import contextlib
import glob
import json
import os
import statistics
import threading
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
_SUB_STAGES = {"hashed": ("enter", "prep", "place", "step", "finish",
                          "leave"),
               "string": ("enter", "hash", "prep", "place", "step",
                          "finish", "leave")}


def _door_cfg(algo: str) -> Config:
    return Config(algorithm=_ALGOS[algo], limit=100, window=60.0,
                  sketch=SketchParams(depth=2, width=2048, sub_windows=8))


@contextlib.contextmanager
def _native_door(algo: str, registry=None):
    """A one-shard native door on a frozen clock (so that two runs of the
    same frames decide the same) with a connected client."""
    lim = create_limiter(_door_cfg(algo), backend="sketch",
                         clock=ManualClock(T0))
    srv = NativeRateLimitServer(lim, "127.0.0.1", 0, max_batch=4096,
                                max_delay=200e-6,
                                registry=registry or m.Registry())
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
        # Ring capacity 1024, 7 rows a dispatch on the completer's ring
        # (its six door stages and, since PR 29, the resolve's "fetch").
        assert len(dispatches) >= 146

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
        covered = []
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
        # What no sub-stage holds is Python of the callback outside the
        # limiter (bookkeeping, delegation): a fixed ~0.1 ms, a few
        # per cent of a dispatch on the chip (dispatch_covered_pct) and
        # a tenth of the ~0.9 ms dispatches of this geometry on a CPU.
        assert statistics.median(covered) >= 0.8, covered

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

    @pytest.mark.parametrize("algo", list(_ALGOS))
    def test_spans_reach_the_profiler_timeline(self, tmp_path, algo):
        """--trace on (a TracingDecorator in the stack): the sub-stage
        spans are TraceMes of the profiler's own timeline, nested in the
        decorator's launch annotation, and the clock anchor is among
        them with the recorder's clock as its argument."""
        import jax.profiler

        from ratelimiter_tpu.observability.decorators import TracingDecorator

        tracing.annotate(False)
        lim = TracingDecorator(create_limiter(
            _door_cfg(algo), backend="sketch", clock=ManualClock(T0)))
        assert tracing.ANNOTATE                   # the decorator IS --trace
        ids = np.arange(1, 17, dtype=np.uint64)
        lim.resolve(lim.launch_ids(ids))          # compile outside the trace
        lim.resolve(lim.launch_hashed(ids))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0              # TraceMes only
        try:
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                before = tracing.now()
                anchor = tracing.clock_anchor()
                after = tracing.now()
                lim.resolve(lim.launch_hashed(ids))
                lim.resolve(lim.launch_ids(ids, wire=True))
            finally:
                jax.profiler.stop_trace()
        finally:
            tracing.annotate(False)
            lim.close()
        assert before <= anchor <= after
        (pb,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        found = [e for plane in jax.profiler.ProfileData.from_file(pb).planes
                 for line in plane.lines for e in line.events
                 if e.name.startswith("ratelimiter/")]
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in found]
        (mark,) = [e for e in found if e.name == "ratelimiter/clock_anchor"]
        assert dict(mark.stats)["mono_ns"] == anchor
        launches = [e for e in events
                    if e[0] == f"ratelimiter/{_ALGOS[algo].value}/launch"]
        assert len(launches) == 2
        for _, lo, hi in launches:
            assert mark.start_ns <= lo            # the anchor came first
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
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d, **kw: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop", None)))
        monkeypatch.setattr(
            tracing, "clock_anchor",
            lambda: calls.append(("anchor", None)) or 1234567)
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
                calls.insert(2, ("body", None))
        finally:
            tracing.annotate(False)
            lim.close()
        assert [c[0] for c in calls] == ["start", "anchor", "body", "stop"]

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
            door = srv.stats()["stage_ns"]
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
        to absorb shared-runner scheduler noise; the tight 3% A/B is a
        bench measurement (``bench.py`` with/without ``--trace``,
        recorded in ADR-014). Off and on rounds alternate on one warm
        limiter and their MEDIANS are compared: a stall of the box under
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


# ----------------------------------------------------- bench integration


class TestBenchTrace:
    def test_loadgen_trace_sampling(self):
        """The e2e loadgen's trace_sample knob (`python -m benchmarks
        --only e2e --trace-sample N`): sampled frames carry wire trace
        ids and land client spans in the local recorder. The server is
        IN-PROCESS here, so its spans share the loadgen's rings — size
        the ring past the scalar-latency pass's span volume or the
        early client spans wrap away (in the real subprocess loadgen
        the client process records only its own spans)."""
        from benchmarks.e2e import _drive

        tracing.disable()
        rec = tracing.enable(1 << 14)
        lim = create_limiter(_sketch_cfg(), backend="sketch")
        try:
            async def run():
                srv = RateLimitServer(lim, max_batch=256,
                                      max_delay=200e-6)
                await srv.start()
                try:
                    return await _drive(srv.port, seconds=0.3, conns=1,
                                        window=64, n_keys=100,
                                        warmup=0.0, trace_sample=1)
                finally:
                    await srv.shutdown()

            out = asyncio.run(run())
            assert out["completed"] > 0
            clients = [s for s in rec.dump() if s["stage"] == "client"]
            assert clients, "no sampled client spans recorded"
            assert all(s["trace_id"] for s in clients)
        finally:
            tracing.disable()
            lim.close()

    def test_stage_breakdown_smoke(self):
        """bench.py --trace block: tiny run, every expected stage key
        present and the hot stages populated."""
        import bench

        tracing.disable()
        out = bench.measure_stage_breakdown(seconds=0.3, batch=256,
                                            width=1 << 11)
        assert tracing.RECORDER is None      # restored the off default
        for stage in ("io", "route", "queue", "coalesce", "launch",
                      "device", "resolve", "encode"):
            assert stage in out["stage_us"]
        assert out["decisions"] > 0
        assert out["stage_us"]["device"] > 0
        assert out["stage_spans"]["io"] > 0
