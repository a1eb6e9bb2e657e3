"""Flight-recorder tracing: per-stage spans across the whole serving path.

The reference plans an OpenTelemetry ``TracingDecorator``
(``docs/ADR/003-decorator-pattern-for-observability.md:115-124``); the
existing ``TracingDecorator`` realizes the device half of that with
``jax.profiler`` annotations, but nothing could attribute ONE frame's
latency to the pipeline stages it crossed (io → route → coalesce →
launch → device → resolve → encode, spanning C++ threads, asyncio
executors, and mesh slices — an early mesh p99 investigation was done
by ad-hoc printf). This module is the missing half: a
flight-recorder of binary span records cheap enough to leave stamped on
the serving hot path.

Design (ADR-014):

* **Per-thread fixed-size ring buffers** of fixed-width records
  (trace_id, stage, shard, batch, t_start/t_end monotonic ns, outcome)
  in a numpy structured array — one row assignment per span, never a
  lock, never an allocation, never I/O on the record path. Rings are
  registered once per OS thread (the only locked operation), keyed by
  ``threading.get_ident()``, and drained only at dump/scrape time. Not
  a ``threading.local``: the native door's C++ threads enter Python
  through ``PyGILState_Ensure``/``Release`` pairs, each pair makes and
  destroys a thread state, and thread-local values die with it — a
  ring per dispatch, kept for good (ADR-014 addendum).
* **Off by default, zero overhead when off**: hot paths read the module
  global ``RECORDER`` once and skip everything — no clock reads, no
  branches beyond the None check, byte-identical decisions either way
  (tests/test_tracing.py pins this).
* **One span primitive, two sinks**: ``span(stage)`` writes a ring row
  when the recorder is on and, when ``--trace`` is on (``annotate``),
  holds a ``jax.profiler.TraceAnnotation("ratelimiter/<stage>")`` open
  for the same interval — the span is then in the device trace, on the
  profiler's clock. Both off: one shared no-op object.
* **Trace context** is a caller-supplied u64 id (0 = unsampled). The
  binary protocol carries it as a flagged extension on any request frame
  (``protocol.with_trace``), HTTP carries W3C ``traceparent``, gRPC the
  same header as metadata, and DCN pushes ride the same frame flag
  outside the HMAC envelope, so one id survives client → server → DCN.
* **Dumps are Perfetto-loadable**: ``chrome_trace()`` renders the Chrome
  trace-event JSON Perfetto/chrome://tracing open directly; spans of one
  frame share its trace id in ``args`` and nest by containment
  (frame ⊃ slice ⊃ device), which is the span-tree oracle the tests
  walk.
* **Histograms ride the scrape**: ``attach_registry`` installs a
  collect hook deriving ``rate_limiter_stage_seconds{stage=...}`` from
  the rings at scrape time (the same seam as the debt-slab gauges) with
  OpenMetrics exemplars tying buckets to the trace ids that landed in
  them.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

log = logging.getLogger("ratelimiter_tpu.tracing")

#: Stage vocabulary (u8 codes in the record). Both doors + the mesh
#: composite use these names; unknown names are rejected loudly so
#: dumps stay joinable across versions.
STAGES = (
    "io",         # wire frame parse + enqueue (reader loop / C++ io thread)
    "route",      # shard/slice partition of a frame
    "queue",      # waiting in the pending queue for the next dispatch
    "coalesce",   # coalescing-window residency (first pending -> flush)
    "launch",     # stage + enqueue the jitted step (non-blocking)
    "dispatch",   # native door: drain -> launch callback returned
    "device",     # block on the device for the oldest in-flight dispatch
    "barrier",    # mesh frame: the single completion barrier (ADR-013)
    "slice",      # mesh frame: one slice's sub-dispatch resolve
    "resolve",    # host bookkeeping after the device fetch
    "complete",   # native door: completer post-processing
    "encode",     # response framing
    None,         # code 12 was "respond": no door ever recorded it (the
                  # responder's time is a sum on the door, stats()
                  # ["stage_ns"]); the slot stays so later codes keep
                  # their value
    "http",       # HTTP gateway decision (traceparent attribution)
    "grpc",       # gRPC decision (traceparent metadata attribution)
    "dcn",        # one DCN push round-trip to a peer
    "client",     # client-side request span (loadgen sampling)
    "forward",    # fleet forward lane: one coalesced wire window's
                  # round trip to a peer (send -> parsed reply), recorded
                  # under the WINDOW-level trace id (ADR-021)
    # The native door's ``dispatch`` stage from inside, in order
    # (sketch-family launch path; ADR-014 addendum):
    "enter",      # C++ drain stamp -> first line of the launch callback;
                  # ring only. Two waits under one name, split exactly by
                  # rate_limiter_door_thread_seconds_total{thread=
                  # "dispatcher"}: state="gather" (the column gather) and
                  # state="gil" (inside PyGILState_Ensure)
    "hash",       # string lane: bulk-hash the drained keys
    "prep",       # staging slot, pad copies, lock waits, rollover check
    "place",      # host -> device placement of the step's operands
    "step",       # the jitted step call returning (enqueue, not execution)
    "finish",     # host limits, finish/pack programs enqueued, ticket filled
    "leave",      # launch callback's last line -> C++ push stamp; ring
                  # only. The GIL's release (the tail of the dispatcher's
                  # state="python") + the wait for an in-flight slot
                  # (state="slot", counted apart)
    # The collective router's launch (parallel/collective.py) uses prep /
    # place / step / finish for the work it shares with the launch above,
    # route for the whole launch, barrier for its resolve, and two stages
    # of its own:
    "assemble",   # the mesh and slice locks taken, the rollover check,
                  # per-slice state leaves gathered into global sharded
                  # arrays (between place and step)
    "writeback",  # each device's output shard installed as its slice's
                  # state leaf (between step and finish)
    # The resolve half of a dispatch (HashedLane._resolve_ticket, the
    # collective router's resolve), on the resolving thread:
    "fetch",      # device ready -> np.asarray returned: the one packed
                  # result buffer fetched (a shard a device) and the wait
                  # to get the GIL back; "unpack" follows. What the
                  # completer waits for the GIL on entry is
                  # ...thread_seconds_total{thread="completer",state="gil"}
    # The dense backend's key directory (ADR-027), under the limiter's
    # lock, inside "prep" when a launch triggers it:
    "reclaim",    # the table-sized reclaim pass enqueued AND waited for
                  # (its count of freed entries is fetched)
    # The launch callback outside the lane's launch; ring only, recorded
    # like "enter" / "leave" from stamps the ticket carries (the
    # callback's in t_door, the lane's first and last in t_lane):
    "descend",    # first line of the callback (after "hash") -> where
                  # "prep" opens: frombuffer, the shard lock, decorators
    "ascend",     # where "finish" closed -> the callback's last line:
                  # the decorators on the way up, depth lock, gauge, hist
    # The second half of what "fetch" was (resolve, completer's thread):
    "unpack",     # np.asarray returned -> BatchResult's columns built
                  # (HashedLane._unpack: one native pass over [:b])
    # Before the server serves (serving/__main__._prewarm), a thread a
    # dispatch target, ``shard`` the target's place among them:
    "prewarm",    # every pad shape of both lanes compiled (or read from
                  # the compile cache) and run once on that target's
                  # device; the slices' spans overlap when they warm
                  # side by side
)
_STAGE_CODE: Dict[str, int] = {s: i for i, s in enumerate(STAGES) if s}

#: Outcome codes.
OK, ERROR, FAIL_OPEN = 0, 1, 2

#: One span record: 32 bytes, fixed width — the ring is a plain numpy
#: structured array so a record is ONE row assignment.
RECORD_DTYPE = np.dtype([
    ("trace_id", "<u8"),
    ("t_start", "<u8"),
    ("t_end", "<u8"),
    ("batch", "<u4"),
    ("shard", "<i2"),
    ("stage", "u1"),
    ("outcome", "u1"),
])


def now() -> int:
    """Monotonic nanoseconds — the span clock. Same CLOCK_MONOTONIC
    domain as the native door's ``steady_clock`` stamps, so C++ and
    Python spans interleave on one timeline."""
    return time.monotonic_ns()


def new_trace_id() -> int:
    """Fresh nonzero sampling id (64-bit; 0 means 'unsampled')."""
    import secrets

    return secrets.randbits(64) | 1


def parse_traceparent(header: Optional[str]) -> int:
    """W3C ``traceparent`` -> u64 trace id (low 8 bytes of the 16-byte
    trace-id field), 0 for absent/malformed headers. Lenient on
    version/flags — attribution must never reject a request."""
    if not header:
        return 0
    parts = header.strip().split("-")
    if len(parts) < 3 or len(parts[1]) != 32:
        return 0
    try:
        return int(parts[1][16:], 16)
    except ValueError:
        return 0


def format_traceparent(trace_id: int) -> str:
    """u64 trace id -> a valid ``traceparent`` header value."""
    return f"00-{trace_id & ((1 << 64) - 1):032x}-{trace_id & ((1 << 64) - 1) or 1:016x}-01"


class _Ring:
    """One thread's span ring. Only its owning thread writes; readers
    take racy-but-consistent numpy copies (each row is written once and
    ``idx`` is published after the row — a torn read can at worst see a
    half-written CURRENT row, which drains skip via t_end==0)."""

    __slots__ = ("buf", "idx", "tid", "name")

    def __init__(self, capacity: int):
        self.buf = np.zeros(capacity, dtype=RECORD_DTYPE)
        self.idx = 0  # total records ever written (monotone)
        self.tid = threading.get_ident()
        name = threading.current_thread().name
        # A thread Python did not create (the native door's dispatcher
        # and completer) only has the placeholder name threading invents.
        self.name = (f"native-{self.tid}" if name.startswith("Dummy-")
                     else name)


class FlightRecorder:
    """Process-wide span recorder over per-thread rings."""

    def __init__(self, capacity: int = 8192):
        if capacity < 16:
            raise ValueError(f"capacity must be >= 16, got {capacity}")
        # Round up to a power of two so the ring index is a mask.
        cap = 1
        while cap < capacity:
            cap <<= 1
        self.capacity = cap
        self._mask = cap - 1
        #: OS thread id -> that thread's ring. Read without a lock on
        #: the record path (a thread only ever looks up its own key),
        #: written under ``_rings_lock``.
        self._rings: Dict[int, _Ring] = {}
        self._rings_lock = threading.Lock()
        self._registries: list = []
        #: Parent-child trace-id links (ADR-021): a fleet forward lane
        #: re-frames member fragments under one WINDOW-level trace id
        #: and records (client frame id -> window id) here, so the
        #: cross-host stitcher can join the receiving member's
        #: window-id spans back to the client frame. Bounded; links are
        #: per-window (rare next to spans), appended under a lock.
        self._links: collections.deque = collections.deque(
            maxlen=max(1024, cap))
        self._links_lock = threading.Lock()

    # ------------------------------------------------------------ record

    def _ring(self) -> _Ring:
        ring = self._rings.get(threading.get_ident())
        if ring is None:
            ring = _Ring(self.capacity)
            with self._rings_lock:
                self._rings[ring.tid] = ring
        return ring

    def record(self, stage, t_start: int, t_end: int, *, trace_id: int = 0,
               shard: int = -1, batch: int = 1, outcome: int = OK) -> None:
        """Stamp one span. Hot-path cost: a dict lookup and one
        structured-row assignment (no locks, no allocation)."""
        ring = self._ring()
        i = ring.idx & self._mask
        ring.buf[i] = (trace_id & 0xFFFFFFFFFFFFFFFF, t_start, t_end,
                       batch & 0xFFFFFFFF, shard,
                       stage if isinstance(stage, int)
                       else _STAGE_CODE[stage], outcome)
        ring.idx += 1

    def link(self, parent_id: int, child_id: int) -> None:
        """Record a parent->child trace-id relation (the fleet forward
        lane's fragment -> wire-window linkage, ADR-021). Not a
        hot-path call: one link per coalesced wire window."""
        if not parent_id or not child_id or parent_id == child_id:
            return
        with self._links_lock:
            self._links.append((parent_id & 0xFFFFFFFFFFFFFFFF,
                                child_id & 0xFFFFFFFFFFFFFFFF, now()))

    def links(self) -> List[dict]:
        """Recorded trace-id links as dicts (ids in the 16-hex trace-id
        rendering)."""
        with self._links_lock:
            snap = list(self._links)
        return [{"parent": f"{p:016x}", "child": f"{c:016x}",
                 "t_ns": t} for p, c, t in snap]

    # ------------------------------------------------------------- drain

    def _snapshot(self):
        """[(ring, entries-copy oldest-first, first_seq)] without
        stopping writers (copies are taken per ring)."""
        with self._rings_lock:
            rings = list(self._rings.values())
        out = []
        for ring in rings:
            idx = ring.idx
            n = min(idx, self.capacity)
            if n == 0:
                continue
            lo = idx & self._mask
            if idx <= self.capacity:
                ent = ring.buf[:n].copy()
            else:
                ent = np.concatenate([ring.buf[lo:], ring.buf[:lo]])
            out.append((ring, ent, idx - n))
        return out

    def dump(self) -> List[dict]:
        """Recent spans (up to capacity per thread) as dicts, sorted by
        t_start. Drain-time work only — never on the record path."""
        spans: List[dict] = []
        for ring, ent, _ in self._snapshot():
            keep = ent[ent["t_end"] != 0]
            for row in keep:
                spans.append({
                    "trace_id": int(row["trace_id"]),
                    "stage": STAGES[int(row["stage"])],
                    "shard": int(row["shard"]),
                    "batch": int(row["batch"]),
                    "t_start_ns": int(row["t_start"]),
                    "t_end_ns": int(row["t_end"]),
                    "outcome": int(row["outcome"]),
                    "thread": ring.name,
                })
        spans.sort(key=lambda s: s["t_start_ns"])
        return spans

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON (Perfetto / chrome://tracing load it
        directly): one complete ("X") event per span, microsecond
        timestamps, trace id / shard / batch / outcome in args."""
        pid = os.getpid()
        events = []
        for ring, ent, _ in self._snapshot():
            keep = ent[ent["t_end"] != 0]
            for row in keep:
                t0 = int(row["t_start"])
                events.append({
                    "name": STAGES[int(row["stage"])],
                    "cat": "ratelimiter",
                    "ph": "X",
                    "ts": t0 / 1e3,
                    "dur": max(int(row["t_end"]) - t0, 0) / 1e3,
                    "pid": pid,
                    "tid": ring.tid,
                    "args": {
                        "trace_id": f"{int(row['trace_id']):016x}",
                        "shard": int(row["shard"]),
                        "batch": int(row["batch"]),
                        "outcome": int(row["outcome"]),
                    },
                })
        events.sort(key=lambda e: e["ts"])
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"clock": "CLOCK_MONOTONIC",
                          "threads": {str(tid): r.name for tid, r
                                      in list(self._rings.items())},
                          # Fragment -> wire-window linkage plus a
                          # (mono, wall) clock stamp, so an offline
                          # stitcher can join and align dumps pulled
                          # from several hosts (fleet/tower.py).
                          "links": self.links(),
                          "mono_ns": now(),
                          "wall_s": time.time()},
        }

    def stage_summary(self) -> Dict[str, dict]:
        """{stage: {count, total_us, mean_us, p99_us}} over the rings
        (what ``rate_limiter_stage_seconds`` exports at scrape)."""
        per: Dict[str, list] = {}
        for _, ent, _ in self._snapshot():
            keep = ent[ent["t_end"] != 0]
            for code in np.unique(keep["stage"]):
                rows = keep[keep["stage"] == code]
                per.setdefault(STAGES[int(code)], []).append(
                    (rows["t_end"] - rows["t_start"]).astype(np.int64))
        out: Dict[str, dict] = {}
        for stage, chunks in per.items():
            ns = np.concatenate(chunks)
            out[stage] = {
                "count": int(ns.size),
                "total_us": round(float(ns.sum()) / 1e3, 1),
                "mean_us": round(float(ns.mean()) / 1e3, 1),
                "p99_us": round(float(np.percentile(ns, 99)) / 1e3, 1),
            }
        return out

    # --------------------------------------------- scrape-time histograms

    def attach_registry(self, registry) -> None:
        """Derive ``rate_limiter_stage_seconds{stage=...}`` from the
        rings via the registry's scrape-time collect-hook seam (the same
        mechanism as the debt-slab gauges, ADR-013): spans recorded since
        the previous scrape are observed into the histogram — WITH an
        OpenMetrics exemplar carrying the span's trace id — once per
        scrape, never on the decide path."""
        hist = registry.histogram(
            "rate_limiter_stage_seconds",
            "Per-stage serving latency derived from the flight recorder "
            "(ADR-014); buckets carry trace-id exemplars in the "
            "OpenMetrics rendering")
        cursors: Dict[int, int] = {}

        def collect() -> None:
            for ring, ent, first_seq in self._snapshot():
                seen = cursors.get(id(ring), 0)
                start = max(seen, first_seq)
                fresh = ent[start - first_seq:]
                fresh = fresh[fresh["t_end"] != 0]
                for row in fresh:
                    dt = max(int(row["t_end"]) - int(row["t_start"]), 0) / 1e9
                    tid = int(row["trace_id"])
                    hist.observe(
                        dt,
                        exemplar=(f"{tid:016x}" if tid else None),
                        stage=STAGES[int(row["stage"])])
                cursors[id(ring)] = first_seq + len(ent)

        registry.add_collect_hook(collect)
        self._registries.append((registry, collect))

    def detach(self) -> None:
        for registry, collect in self._registries:
            registry.remove_collect_hook(collect)
        self._registries.clear()


#: Process-wide recorder; None = tracing off (the default). Hot paths
#: read this module global once per operation and skip everything when
#: it is None — that None check IS the documented overhead budget.
RECORDER: Optional[FlightRecorder] = None


def enable(capacity: int = 8192, registry=None) -> FlightRecorder:
    """Turn the flight recorder on (idempotent); optionally attach the
    scrape-time stage histograms to ``registry``."""
    global RECORDER
    if RECORDER is None:
        RECORDER = FlightRecorder(capacity)
    if registry is not None:
        RECORDER.attach_registry(registry)
    return RECORDER


def disable() -> None:
    """Turn tracing off and unhook any scrape-time collectors."""
    global RECORDER
    if RECORDER is not None:
        RECORDER.detach()
    RECORDER = None


def get() -> Optional[FlightRecorder]:
    return RECORDER


def record(stage, t_start: int, t_end: int, **kw) -> None:
    """Convenience guarded record (hot paths inline the None check and
    call ``RECORDER.record`` directly instead)."""
    rec = RECORDER
    if rec is not None:
        rec.record(stage, t_start, t_end, **kw)


# ------------------------------------------------------ span primitive

#: ``--trace`` (``annotate``; a ``TracingDecorator`` in the stack turns
#: it on): every ``span`` also writes a TraceMe into the profiler's
#: timeline.
ANNOTATE = False


def annotate(on: bool = True) -> None:
    """Turn the profiler sink of ``span`` on or off."""
    global ANNOTATE
    ANNOTATE = bool(on)


class _NoSpan:
    """What ``span`` returns when both sinks are off: one shared object,
    no clock read, no allocation."""

    __slots__ = ()
    t_open = t_close = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def next(self, stage: str) -> None:
        pass


NO_SPAN = _NoSpan()


def _trace_me(name: str, **args):
    """The one place a ``ratelimiter/*`` TraceMe is made."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(f"ratelimiter/{name}", **args)


def annotation(name: str):
    """Profiler-only span ``ratelimiter/<name>``: ``TracingDecorator``'s
    per-call annotations, whose names carry the algorithm and whose
    intervals the doors already record as ring rows (``launch``,
    ``dispatch``, ``resolve``)."""
    return _trace_me(name) if ANNOTATE else NO_SPAN


class _Span:
    """One open stage with up to two sinks: a ring row at exit (recorder
    on) and a TraceMe held open for the same interval (``--trace`` on).
    ``t_open`` / ``t_close``: the stamps of its first stage's start and
    its last stage's end (0 with the recorder off), for a caller whose
    own rows begin and end there."""

    __slots__ = ("_rec", "_annotate", "_ann", "_stage", "_t0", "_trace_id",
                 "_shard", "_batch", "t_open", "t_close")

    def __init__(self, rec, annotate_on, stage, trace_id, shard, batch):
        self._rec = rec
        self._annotate = annotate_on
        self._ann = None
        self._stage = stage
        self._t0 = self.t_open = self.t_close = 0
        self._trace_id = trace_id
        self._shard = shard
        self._batch = batch

    def __enter__(self):
        if self._annotate:
            self._ann = _trace_me(self._stage)
            self._ann.__enter__()
        if self._rec is not None:
            self._t0 = self.t_open = now()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._rec is not None:
            self.t_close = now()
            self._row(self.t_close, ERROR if exc_type is not None else OK)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        return False

    def next(self, stage: str) -> None:
        """Close the open stage and open ``stage`` at the same instant
        (one clock read): consecutive stages of one function that
        straddle its ``with`` and ``try`` blocks — a lock taken in one
        stage, dropped in a later one — take one ``with`` and a ``next``
        per boundary."""
        if self._rec is not None:
            t = now()
            self._row(t, OK)
            self._t0 = t
        self._stage = stage
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = _trace_me(stage)
            self._ann.__enter__()

    def _row(self, t_end: int, outcome: int) -> None:
        self._rec.record(self._stage, self._t0, t_end,
                         trace_id=self._trace_id, shard=self._shard,
                         batch=self._batch, outcome=outcome)


def span(stage: str, *, shard: int = -1, batch: int = 1, trace_id: int = 0):
    """The one way the program opens a host span: a context manager for
    ``stage`` on the calling thread. Without a ``trace_id`` / ``shard``
    the row takes the thread's current ones (``set_current``): layers
    below a door have no such parameters."""
    rec = RECORDER
    if rec is None:
        if not ANNOTATE:
            return NO_SPAN
    else:
        if not trace_id:
            trace_id = current()
        if shard < 0:
            shard = getattr(_CTX, "shard", -1)
    return _Span(rec, ANNOTATE, stage, trace_id, shard, batch)


# ------------------------------------------------- profiler start/stop


def clock_anchor() -> int:
    """Write ``ratelimiter/clock_anchor`` into the running capture with
    this module's clock as its ``mono_ns`` argument, and return that
    reading: the TraceMe's start on the profiler's timeline IS that
    CLOCK_MONOTONIC instant, so rows stamped outside Python (the native
    door's ``io`` / ``dispatch`` / ``device`` / ``complete``) can be laid
    on the profile by one offset."""
    mono_ns = now()
    with _trace_me("clock_anchor", mono_ns=mono_ns):
        pass
    return mono_ns


class Anchors:
    """A capture's two readings of ``clock_anchor``: ``start`` just after
    the trace started, ``end`` just before it stopped (None until then).
    Each is one instant on both clocks, so the two bound the drift
    between CLOCK_MONOTONIC and the profiler's timeline over the
    capture."""

    __slots__ = ("start", "end")

    def __init__(self, start: int):
        self.start = start
        self.end: Optional[int] = None


@contextlib.contextmanager
def profile(out_dir: str, *, python_tracer: bool = False):
    """One ``jax.profiler`` capture into ``out_dir`` (xplane format):
    the start/stop behind ``TracingDecorator.capture`` and
    ``/debug/profile``. Yields the capture's ``Anchors``.

    The capture holds host TraceMes (``ratelimiter/*``, ``PjitFunction``,
    the runtime's transfers) and the device planes. It does NOT hold
    Python frames unless ``python_tracer`` asks for them: the profiler's
    Python tracer hooks every call and return of every thread, which
    cost the server it read ~10 % of its rate while the capture ran, at
    a 1.7 ms dispatch cycle (PERF.md §6, PR 37; what else a traced run
    loses is the recorder's rows, the TraceMes and the stop's decoding)
    — for an operator hunting a Python hot spot, not for timing a
    run."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1 if python_tracer else 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    anchors = None
    try:
        anchors = Anchors(clock_anchor())
        yield anchors
    finally:
        if anchors is not None:
            anchors.end = clock_anchor()
        # Stopping collects and writes the trace, and that grows with the
        # device programs captured (every op of every execution on every
        # chip): minutes for a few seconds of a busy mesh. Logged, so
        # whoever waits for /debug/profile can size their timeout.
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        log.info("profile capture in %s: stop_trace took %.1fs", out_dir,
                 time.monotonic() - t0)


# ----------------------------------------------- current-trace context
#
# A thread-local "trace id of the work currently being launched": the
# micro-batcher sets it (recorder-on only) around the limiter launch
# call, so layers WITHOUT a trace-id parameter in their signature — the
# fleet forwarder splitting a frame onto peer lanes is the one that
# matters (ADR-021) — can attribute the rows they ship. For a coalesced
# window the id is the window's representative (first sampled frame),
# the same id its coalesce/launch/device spans carry.

_CTX = threading.local()


def set_current(trace_id: int, shard: int = -1) -> None:
    _CTX.trace_id = trace_id
    _CTX.shard = shard


def current() -> int:
    """Trace id of the frame/window being launched on this thread
    (0 = none/unsampled)."""
    return getattr(_CTX, "trace_id", 0)
