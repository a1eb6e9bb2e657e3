"""Micro-batching dispatcher: many concurrent RPCs -> one device call.

This is the TPU-native analog of the reference's "Redis serializes all
Lua scripts" (SURVEY.md §2.6): where the reference pays one network
round-trip per decision and lets Redis order them, the front door
coalesces every request that arrives within ``max_delay`` (or until
``max_batch`` is reached) into ONE ``allow_batch`` dispatch, whose in-batch
segment sequencing (ops/segment.py) provides exactly the serialized
semantics. BASELINE.json's north star assumes this shape (batch 4096).

Policy knobs (ADR-002 analog at the dispatch layer):

* dispatch failure: handled inside the limiter (fail-open allowance or
  StorageUnavailableError per Config.fail_open);
* SLO breach (``dispatch_timeout``): if one dispatch takes longer than the
  timeout, waiting requests stop waiting — fail-open configs answer
  "allowed (fail_open)" immediately, fail-closed configs get
  StorageUnavailableError. The device call itself is NOT cancelled: its
  state update still lands (over-admission is bounded by the documented
  fail-open contract), and the batcher keeps serving.

Thread model: the event loop owns the queue; a single-threaded *launch*
executor owns the non-blocking half of each dispatch (stage + enqueue
the jitted step via the limiter's launch/resolve API, ADR-010) and a
single-threaded *resolve* executor blocks on the oldest in-flight
result, so up to ``inflight`` dispatches overlap on the device while the
loop keeps coalescing. Backends without a pipelined path (exact/dense)
fall back to the original one-executor allow_batch dispatch.

Coalescing is queue-depth-aware (continuous batching, Orca/vLLM style):
``max_delay`` is the idle coalescing window; as the pending queue fills
toward ``max_batch`` the flush timer is pulled earlier, so a deep queue
never waits the full delay for a batch it could fill immediately.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from ratelimiter_tpu.algorithms.base import RateLimiter, check_key, check_n
from ratelimiter_tpu.core.errors import (
    DeadlineExceededError,
    InvalidConfigError,
    InvalidNError,
    StorageUnavailableError,
)
from ratelimiter_tpu.core.types import (
    BatchResult,
    Result,
    batch_fail_open,
    fail_open_result,
)
from ratelimiter_tpu.observability import audit, tracing
from ratelimiter_tpu.observability import metrics as m


class MicroBatcher:
    """Coalesce concurrent allow/allow_n calls into batched dispatches.

    Args:
        limiter: any RateLimiter (decorated or not).
        max_batch: flush as soon as this many requests are pending
            (BASELINE config 3 serving shape: 4096).
        max_delay: flush this many seconds after the first pending request
            (the latency the batcher may add to coalesce; default 200 µs).
            With ``adaptive_delay`` this is the IDLE window — a queue
            filling toward max_batch flushes proportionally sooner.
        dispatch_timeout: SLO for one dispatch, seconds; None disables.
        inflight: launched-but-unresolved dispatch window for pipelined
            backends (launch/resolve API); launches past the window block
            in the launch executor (backpressure). 1 disables overlap.
        adaptive_delay: queue-depth-aware coalescing (on by default).
        registry: metrics registry for queue/batch/SLO gauges.
    """

    def __init__(self, limiter: RateLimiter, *, max_batch: int = 4096,
                 max_delay: float = 200e-6,
                 dispatch_timeout: Optional[float] = None,
                 inflight: int = 8, adaptive_delay: bool = True,
                 registry: Optional[m.Registry] = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if inflight < 1:
            raise ValueError(f"inflight must be >= 1, got {inflight}")
        self.limiter = limiter
        self.max_batch = max_batch
        self.max_delay = max_delay
        self.dispatch_timeout = dispatch_timeout
        self.inflight = inflight
        self.adaptive_delay = adaptive_delay
        self._pending: List[Tuple[str, int, asyncio.Future, float]] = []
        #: Queued ALLOW_HASHED frames awaiting the next coalescing window
        #: (scatter-gather scheduling, ADR-013): (ids, ns, future,
        #: trace_id) per frame; flushed alongside the string queue into
        #: ONE launch per window, each frame answered from its
        #: contiguous row range. Residency is traced at WINDOW level
        #: (_q_t0) — per-frame residency spans would overlap on the
        #: event-loop thread and break the per-thread span invariant.
        self._pending_hashed: List[tuple] = []
        self._pending_hashed_ids = 0
        #: Fleet forward-lane windows (protocol.FORWARD_FLAG, ADR-019):
        #: coalesced SEPARATELY from the client lanes. Forward windows
        #: hold only locally-owned rows, so merging them with each
        #: other is safe batching — but merging them into a window
        #: that also holds client rows needing onward forwarding would
        #: couple the forward reply to OUR peers' progress (an
        #: unbounded cross-host dependency chain under mixed traffic).
        self._pending_fwd: List[tuple] = []
        self._pending_fwd_ids = 0
        #: Flight-recorder window context (ADR-014): first-enqueue stamp
        #: and the first sampled trace id of the current coalescing
        #: window. Zero cost while tracing is off (RECORDER is None).
        self._q_t0 = 0
        self._q_trace = 0
        self._timer: Optional[asyncio.TimerHandle] = None
        self._first_ts = 0.0
        self._armed_depth = 0
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        #: Re-arm points for the adaptive timer (power-of-two-ish depths;
        #: re-arming per submit would churn call_later on the hot loop).
        #: Crossing detection, not equality: batch frames jump the depth
        #: by whole frames and would hop over an exact-match check.
        self._adaptive_marks = sorted(
            {d for d in (max_batch // 8, max_batch // 4, max_batch // 2,
                         (3 * max_batch) // 4) if d >= 2})
        # Pipelining and the dispatch SLO are mutually exclusive (same
        # rule as the native door): the SLO guarantee is "waiters are
        # answered by the deadline even when the device hangs", and a
        # launch blocked on a full in-flight window sits OUTSIDE any
        # wait_for — its waiters would hang past the SLO.
        self._pipelined = bool(getattr(limiter, "pipelined", False)
                               and inflight > 1
                               and dispatch_timeout is None)
        # Lane support is a property of the BACKEND, not the decorator
        # stack (decorators delegate the whole raw-id surface, so a
        # hasattr on the decorated limiter is always true).
        from ratelimiter_tpu.observability.decorators import undecorated

        self._hashed_lane = hasattr(undecorated(limiter), "allow_ids")
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="rl-dispatch")
        if self._pipelined:
            # Separate single-thread stages keep launch order == resolve
            # order (both executors are FIFO) while batch k's blocking
            # resolve overlaps batch k+1's launch.
            self._resolve_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="rl-resolve")
            self._window = threading.Semaphore(inflight)
        else:
            self._resolve_pool = None
            self._window = None
        #: Side pool for FLEET tickets (ADR-017): a frame whose resolve
        #: must wait on a peer's answer (forwarded rows) may NOT occupy
        #: the FIFO resolve executor — inbound forwarded frames from
        #: that same peer resolve there, and two members blocking their
        #: pipelines on each other is a distributed deadlock (observed
        #: under symmetric mixed load). Remote-merge frames also give
        #: their in-flight window slot back before the wait: the window
        #: bounds DEVICE dispatches, and a network wait holding a slot
        #: recreates the same cycle one layer down. Lazily built — zero
        #: cost for non-fleet deployments.
        self._fleet_pool: Optional[
            concurrent.futures.ThreadPoolExecutor] = None
        self._depth = 0
        self._depth_lock = threading.Lock()
        self._inflight: set = set()
        self._draining = False
        self.decisions_total = 0

        reg = registry if registry is not None else m.DEFAULT
        self._queue_depth = reg.gauge(
            "rate_limiter_server_queue_depth",
            "Requests waiting for the next batched dispatch")
        self._dispatch_batch = reg.histogram(
            "rate_limiter_server_batch_size",
            "Requests per batched dispatch", m.BATCH_BUCKETS)
        self._dispatch_latency = reg.histogram(
            "rate_limiter_server_dispatch_seconds",
            "Wall time of one batched device dispatch", m.LATENCY_BUCKETS)
        self._slo_breaches = reg.counter(
            "rate_limiter_server_slo_breaches_total",
            "Dispatches that exceeded dispatch_timeout")
        self._slo_breach_decisions = reg.counter(
            "rate_limiter_server_slo_breach_decisions_total",
            "Decisions answered by SLO-breach policy (fail-open/closed) "
            "instead of a device result — the DECISION-unit form of "
            "slo_breaches_total (one breached frame is up to max_batch "
            "of these; the burn tracker's availability axis consumes "
            "this one, ADR-016)")
        self._deadline_shed = reg.counter(
            "rate_limiter_server_deadline_shed_total",
            "Decisions shed because their propagated deadline expired "
            "before dispatch (answered per fail-open/closed, ADR-015)")
        self._inflight_gauge = reg.gauge(
            "rate_limiter_pipeline_inflight",
            "Launched device dispatches not yet resolved (pipelined "
            "serving hot path, ADR-010)")
        self._launch_hist = reg.histogram(
            "rate_limiter_pipeline_launch_seconds",
            "Launch phase wall time (stage + enqueue, non-blocking)",
            m.LATENCY_BUCKETS)
        self._resolve_hist = reg.histogram(
            "rate_limiter_pipeline_resolve_seconds",
            "Resolve phase wall time (block on the oldest in-flight "
            "result + host conversion)", m.LATENCY_BUCKETS)

    def _depth_add(self, d: int) -> None:
        with self._depth_lock:
            self._depth += d
            self._inflight_gauge.set(float(self._depth))

    # ------------------------------------------------------------ submit

    def _note_window(self, trace_id: int) -> None:
        """Window trace context: stamp the first-enqueue time once per
        coalescing window and keep the first sampled trace id."""
        if tracing.RECORDER is not None and not self._q_t0:
            self._q_t0 = tracing.now()
        if trace_id and not self._q_trace:
            self._q_trace = trace_id

    def _enqueue(self, loop: asyncio.AbstractEventLoop, key: str,
                 n: int, trace_id: int = 0,
                 deadline: float = 0.0) -> asyncio.Future:
        fut: asyncio.Future = loop.create_future()
        self._pending.append((key, n, fut, deadline))
        self._note_window(trace_id)
        if len(self._pending) >= self.max_batch:
            self._flush()
        return fut

    # -------------------------------------------------- deadline shedding

    def _shed_frame(self, fut: asyncio.Future, b: int) -> None:
        """Answer one whole hashed frame (``b`` decisions) whose
        propagated deadline expired before dispatch, per the
        fail-open/closed policy (ADR-015) — nobody is waiting for the
        real answer, so the dispatch slot is not burned."""
        self._deadline_shed.inc(b)
        cfg = self.limiter.config
        if fut.done():
            return
        if cfg.fail_open:
            reset_at = self.limiter.clock.now() + float(cfg.window)
            fut.set_result(batch_fail_open(b, cfg.limit, reset_at))
        else:
            fut.set_exception(DeadlineExceededError(
                "request deadline expired before dispatch"))

    def _shed_scalar(self, fut: asyncio.Future) -> None:
        """Scalar (string-lane) flavor of deadline shedding."""
        self._deadline_shed.inc()
        cfg = self.limiter.config
        if fut.done():
            return
        if cfg.fail_open:
            fut.set_result(fail_open_result(
                cfg.limit, self.limiter.clock.now() + float(cfg.window)))
        else:
            fut.set_exception(DeadlineExceededError(
                "request deadline expired before dispatch"))

    def _arm_timer(self, loop: asyncio.AbstractEventLoop) -> None:
        # Queue depth counts BOTH lanes in max_batch units: pending
        # string decisions plus queued hashed-frame ids — the adaptive
        # window reacts to total offered load, whichever door it enters.
        depth = (len(self._pending) + self._pending_hashed_ids
                 + self._pending_fwd_ids)
        self._queue_depth.set(depth)
        if not depth:
            return
        if self._timer is None:
            self._first_ts = loop.time()
            self._armed_depth = depth
            delay = self.max_delay
            if self.adaptive_delay and depth > 1:
                # A whole frame landing on an idle queue arms directly at
                # its depth-scaled delay — same curve as the re-arm path.
                delay = self.max_delay * max(0.0,
                                             1.0 - depth / self.max_batch)
            self._timer = loop.call_later(delay, self._flush)
        elif self.adaptive_delay and any(
                self._armed_depth < mk <= depth
                for mk in self._adaptive_marks):
            # Queue-depth-aware coalescing: pull the flush earlier as the
            # queue fills — at depth d the wait shrinks to
            # max_delay * (1 - d/max_batch) measured from the FIRST
            # pending request, so a deep queue never idles out the full
            # window it could already fill (continuous batching).
            target = (self._first_ts
                      + self.max_delay * (1.0 - depth / self.max_batch))
            self._armed_depth = depth
            self._timer.cancel()
            self._timer = loop.call_later(max(0.0, target - loop.time()),
                                          self._flush)

    def submit_nowait(self, key: str, n: int = 1, trace_id: int = 0,
                      deadline: float = 0.0) -> asyncio.Future:
        """Queue one decision and return its future WITHOUT awaiting —
        the zero-task fast path the server's reader loop uses (a done
        callback writes the response; no coroutine per request).
        Validation happens here, before batching, so malformed requests
        fail fast and never poison a batch (reference pre-Redis guards,
        ``tokenbucket.go:91-93``). Must run on the event loop thread.
        ``trace_id`` (ADR-014) samples the window this decision joins
        into the flight recorder. ``deadline`` (ADR-015, absolute
        ``time.monotonic`` seconds; 0 = none): work whose deadline has
        expired is SHED — answered per policy at enqueue or dispatch
        time instead of burning a dispatch slot."""
        if self._draining:
            raise StorageUnavailableError("server is shutting down")
        check_key(key)
        check_n(n)
        loop = asyncio.get_running_loop()
        self._loop = loop
        if deadline and time.monotonic() >= deadline:
            fut: asyncio.Future = loop.create_future()
            self._shed_scalar(fut)
            return fut
        fut = self._enqueue(loop, key, n, trace_id, deadline)
        self._arm_timer(loop)
        return fut

    def submit_many_nowait(self, pairs, trace_id: int = 0,
                           deadline: float = 0.0) -> List[asyncio.Future]:
        """Queue a whole frame of (key, n) decisions atomically: every
        pair is validated BEFORE any is queued, so a bad pair mid-frame
        cannot leave earlier pairs consuming quota with nobody reading
        their futures. Must run on the event loop thread."""
        pairs = list(pairs)
        if self._draining:
            raise StorageUnavailableError("server is shutting down")
        for key, n in pairs:
            check_key(key)
            check_n(n)
        loop = asyncio.get_running_loop()
        self._loop = loop
        if deadline and time.monotonic() >= deadline:
            futs = [loop.create_future() for _ in pairs]
            for f in futs:
                self._shed_scalar(f)
            return futs
        futs = [self._enqueue(loop, key, n, trace_id, deadline)
                for key, n in pairs]
        self._arm_timer(loop)
        return futs

    async def submit(self, key: str, n: int = 1, *,
                     trace_id: int = 0, deadline: float = 0.0) -> Result:
        """Queue one decision; resolves when its batch's dispatch lands."""
        return await self.submit_nowait(key, n, trace_id, deadline)

    # ------------------------------------------------- hashed bulk lane

    def submit_hashed_nowait(self, ids: np.ndarray, ns: np.ndarray,
                             trace_id: int = 0,
                             deadline: float = 0.0,
                             standalone: bool = False) -> asyncio.Future:
        """Queue one whole ALLOW_HASHED frame into the current coalescing
        window (the zero-copy bulk lane, ADR-011 + the scatter-gather
        scheduler, ADR-013): every hashed frame queued within
        ``max_delay`` (adaptive, shared with the string lane) merges into
        ONE ``launch_ids`` dispatch — on a sliced mesh backend that is
        one padded sub-dispatch per touched device per window instead of
        one fork-join per frame. Each frame's future resolves to its
        contiguous row range of the window's BatchResult (wire buffers
        ride along zero-copy). Rides the SAME launch/resolve executors
        and in-flight window as the string path, so pipelining,
        backpressure and FIFO state threading are shared. Must run on
        the event loop thread; requires a limiter exposing the raw-id
        lane (sketch-family backends)."""
        if self._draining:
            raise StorageUnavailableError("server is shutting down")
        if not self._hashed_lane:
            raise InvalidConfigError(
                "the hashed bulk lane requires a sketch-family backend "
                "(raw-id decisions need device-side hashing)")
        if ids.shape[0] and int(ns.min()) <= 0:
            raise InvalidNError("n must be a positive integer")
        loop = asyncio.get_running_loop()
        self._loop = loop
        fut: asyncio.Future = loop.create_future()
        if deadline and ids.shape[0] and time.monotonic() >= deadline:
            # Already expired at parse: answer per policy NOW (ADR-015).
            self._shed_frame(fut, int(ids.shape[0]))
            return fut
        if not ids.shape[0]:
            # count == 0 frames are valid (empty RESULT_HASHED), no
            # dispatch needed.
            fut.set_result(BatchResult(
                allowed=np.zeros(0, dtype=bool),
                limit=self.limiter.config.limit,
                remaining=np.zeros(0, dtype=np.int64),
                retry_after=np.zeros(0, dtype=np.float64),
                reset_at=np.zeros(0, dtype=np.float64)))
            return fut
        b = int(ids.shape[0])
        if b > 2 * self.max_batch:
            # A LONE frame past the largest prewarmed pad shape
            # (2*max_batch) would land an XLA compile on the hot path —
            # the same r06 collapse mode the window guard below
            # prevents for concatenated windows, reachable here because
            # the wire protocol admits frames up to MAX_FRAME (~87K
            # ids) regardless of --max-batch. Mirror the native door's
            # dispatcher carve: flush the pending window (arrival order
            # across dispatches), dispatch max_batch segments in order
            # through the same FIFO executors (same-key sequencing
            # across segments is exactly sequential-dispatch order),
            # and reassemble host-side (fail_open ORs over segments,
            # same contract as the native BatchJoin; the merged result
            # carries no packed wire buffers, so the encoder
            # takes its packbits path — one host re-pack on a frame
            # shape that is rare by construction).
            if self._pending_hashed or self._pending_fwd:
                self._flush()
            seg_futs: List[asyncio.Future] = []
            for off in range(0, b, self.max_batch):
                sfut: asyncio.Future = loop.create_future()
                seg_futs.append(sfut)
                task = asyncio.ensure_future(self._dispatch_hashed(
                    ids[off:off + self.max_batch],
                    ns[off:off + self.max_batch], sfut, trace_id))
                self._inflight.add(task)
                task.add_done_callback(self._inflight.discard)
            join = asyncio.ensure_future(self._join_segments(seg_futs, fut))
            self._inflight.add(join)
            join.add_done_callback(self._inflight.discard)
            return fut
        if standalone:
            # Fleet forward-lane window (protocol.FORWARD_FLAG,
            # ADR-019): wholly owned by this host, while the CLIENT
            # window may hold rows whose resolve waits on OUR forward
            # legs. Coalescing the two would couple this reply to a
            # peer's progress — under symmetric mixed fleet traffic
            # that dependency chain extends without bound (each reply
            # waiting on legs of a window formed later: second-long
            # tails and the 4-host forward-deadline expiry).
            # Forward windows therefore coalesce in their OWN buffer —
            # with each other (windows from 3 peers merge into one
            # dispatch at n >= 4, where per-peer windows shrink to
            # 1/(n-1) of the 2-host size) but never with client rows.
            # b <= 2*max_batch here (the carve above already segmented
            # larger frames), so pad shapes stay prewarmed.
            if (self._pending_fwd
                    and self._pending_fwd_ids + b > 2 * self.max_batch):
                self._flush_fwd()
            self._pending_fwd.append((ids, ns, fut, trace_id, deadline))
            self._pending_fwd_ids += b
            if self._pending_fwd_ids >= self.max_batch:
                self._flush_fwd()
            else:
                self._arm_timer(loop)
            return fut
        if (self._pending_hashed
                and self._pending_hashed_ids + b > 2 * self.max_batch):
            # Coalescing must never produce a window larger than the
            # largest prewarmed pad shape (2*max_batch — the allowance
            # for a lone oversized wire frame): concatenating past it
            # would land an XLA compile on the hot path, the exact r06
            # collapse mode ADR-013 exists to prevent. Flush the current
            # window first; the oversized frame then dispatches alone
            # (arrival order across dispatches is preserved).
            self._flush()
        self._pending_hashed.append((ids, ns, fut, trace_id, deadline))
        self._pending_hashed_ids += b
        self._note_window(trace_id)
        if self._pending_hashed_ids >= self.max_batch:
            self._flush()
        else:
            self._arm_timer(loop)
        return fut

    def _launch_hashed_work(self, ids, ns, trace_id=0, t_q=0):
        """Hashed-frame launch stage (launch executor thread): same
        in-flight window as _launch_work; wire=True has resolve pack the
        response buffers (core/types.wire_pack)."""
        self._window.acquire()
        rec = tracing.RECORDER
        tq0 = tracing.now() if rec is not None else 0
        t0 = time.perf_counter()
        if rec is not None:
            # Current-trace context for layers below without a trace-id
            # parameter (the fleet forwarder links forwarded fragments
            # to this id, ADR-021). Recorder-on only — off stays
            # byte-identical.
            tracing.set_current(trace_id)
        try:
            ticket = self.limiter.launch_ids(ids, ns, wire=True)
        except BaseException:
            self._window.release()
            raise
        finally:
            if rec is not None:
                tracing.set_current(0)
        self._launch_hist.observe(time.perf_counter() - t0)
        if rec is not None:
            # "queue" = waiting for the FIFO launch executor + window
            # slot; "launch" = stage + enqueue of the jitted step.
            if t_q:
                rec.record("queue", t_q, tq0, trace_id=trace_id,
                           batch=int(ids.shape[0]))
            rec.record("launch", tq0, tracing.now(), trace_id=trace_id,
                       batch=int(ids.shape[0]))
        ticket.trace_id = trace_id
        self._depth_add(1)
        return ticket

    def _allow_work(self, keys, ns, trace_id=0, hashed=False):
        """Blocking decide (non-pipelined backends): one "device" span
        covers the whole synchronous dispatch."""
        rec = tracing.RECORDER
        t0 = tracing.now() if rec is not None else 0
        if rec is not None:
            tracing.set_current(trace_id)
        try:
            out = (self.limiter.allow_ids(keys, ns) if hashed
                   else self.limiter.allow_batch(keys, ns))
        finally:
            if rec is not None:
                tracing.set_current(0)
        if rec is not None:
            rec.record("device", t0, tracing.now(), trace_id=trace_id,
                       batch=len(out),
                       outcome=tracing.FAIL_OPEN if out.fail_open
                       else tracing.OK)
        return out

    async def _dispatch_hashed(self, ids, ns, fut: asyncio.Future,
                               trace_id: int = 0) -> None:
        b = int(ids.shape[0])
        self._dispatch_batch.observe(float(b))
        loop = asyncio.get_running_loop()
        t_q = tracing.now() if tracing.RECORDER is not None else 0
        # Audit timestamp fallback, captured at dispatch entry (the
        # pipelined ticket's launch-time t_sec is preferred below).
        t_tap = (self.limiter.clock.now() if audit.AUDITOR is not None
                 else 0.0)
        ticket = None
        t0 = time.perf_counter()
        if self._pipelined and self._hashed_lane:
            try:
                ticket = await loop.run_in_executor(
                    self._pool, self._launch_hashed_work, ids, ns,
                    trace_id, t_q)
            except Exception as exc:
                if not fut.done():
                    fut.set_exception(exc)
                return
            work = self._resolve_target(loop, ticket)
        else:
            work = loop.run_in_executor(
                self._pool,
                lambda: self._allow_work(ids, ns, trace_id, hashed=True))
        timed_out = False
        try:
            if self.dispatch_timeout is not None:
                out = await asyncio.wait_for(
                    asyncio.shield(work), self.dispatch_timeout)
            else:
                out = await work
        except asyncio.TimeoutError:
            timed_out = True
        except Exception as exc:
            if not fut.done():
                fut.set_exception(exc)
            return
        finally:
            self._dispatch_latency.observe(time.perf_counter() - t0)

        if timed_out:
            # Same SLO-breach policy as the string path (ADR-002 at the
            # dispatch layer): answer NOW per fail-open/closed.
            self._slo_breaches.inc()
            self._slo_breach_decisions.inc(b)
            cfg = self.limiter.config
            if cfg.fail_open:
                reset_at = self.limiter.clock.now() + float(cfg.window)
                if not fut.done():
                    fut.set_result(batch_fail_open(b, cfg.limit, reset_at))
                self.decisions_total += b
            else:
                err = StorageUnavailableError(
                    f"dispatch exceeded SLO "
                    f"({self.dispatch_timeout * 1e3:.1f} ms)")
                if not fut.done():
                    fut.set_exception(err)
            # The shielded device call still lands and CONSUMES the
            # frame's sketch mass — mirror its eventual result into the
            # audit tap (ADR-016) so audited keys' shadow timelines
            # don't develop holes that read as false denies later; the
            # callback also keeps the un-awaited error from leaking.
            t_dec = getattr(ticket, "t_sec", 0.0) or t_tap

            def _late_tap(f, _ids=ids, _ns=ns, _t=t_dec):
                if f.exception() is not None:
                    return
                aud = audit.AUDITOR
                if aud is not None:
                    aud.offer_ids(_ids, _ns, _t, f.result())

            work.add_done_callback(_late_tap)
            return

        self.decisions_total += b
        # Live accuracy tap (ADR-016): mirror the resolved frame into
        # the shadow-oracle queue — one None check when auditing is off
        # (byte-identical hot path, same seam as tracing.RECORDER), one
        # bounded-queue append of existing references when on. Sampling
        # and hashing happen on the audit worker, never here. The
        # timestamp is the LAUNCH-time now the sketch decided with
        # (ticket.t_sec), not resolve time.
        aud = audit.AUDITOR
        if aud is not None:
            aud.offer_ids(ids, ns,
                          getattr(ticket, "t_sec", 0.0) or t_tap, out)
        if not fut.done():
            fut.set_result(out)

    async def _join_segments(self, seg_futs: List[asyncio.Future],
                             fut: asyncio.Future) -> None:
        """Reassemble a carved oversized hashed frame (ADR-013): await
        every segment dispatch and answer the frame's future with the
        host-side concatenation. Any segment error fails the whole
        frame (a partial answer would mis-align the columnar reply);
        ``fail_open`` ORs over segments and per-request ``limits``
        materialize wherever any segment carried overrides — both the
        same contracts as the native door's multi-segment BatchJoin."""
        outs = await asyncio.gather(*seg_futs, return_exceptions=True)
        exc = next((o for o in outs if isinstance(o, BaseException)), None)
        if exc is not None:
            if not fut.done():
                fut.set_exception(exc)
            return
        merged = BatchResult(
            allowed=np.concatenate([o.allowed for o in outs]),
            limit=outs[0].limit,
            remaining=np.concatenate([o.remaining for o in outs]),
            retry_after=np.concatenate([o.retry_after for o in outs]),
            reset_at=np.concatenate([o.reset_at for o in outs]),
            fail_open=any(o.fail_open for o in outs),
            limits=(np.concatenate(
                [o.limits if o.limits is not None
                 else np.full(len(o), o.limit, dtype=np.int64)
                 for o in outs])
                if any(o.limits is not None for o in outs) else None))
        if not fut.done():
            fut.set_result(merged)

    async def _dispatch_hashed_window(self, frames) -> None:
        """Dispatch one coalescing window of hashed frames (ADR-013): a
        single-frame window keeps the exact frame-as-batch path; a
        multi-frame window concatenates in ARRIVAL order (same-key
        sequencing across a connection's back-to-back frames is
        preserved — in-batch segment ordering decides duplicates exactly
        as sequential dispatches would), launches ONCE, and answers each
        frame from its contiguous row range of the window result
        (BatchResult.rows — numpy views + row-offset wire buffers, no
        re-packing)."""
        # Deadline shedding at the dispatch boundary (ADR-015): frames
        # whose propagated deadline expired while queued in the
        # coalescing window are answered per policy and never join the
        # dispatch.
        now_mono = time.monotonic()
        expired = [f for f in frames if f[4] and now_mono >= f[4]]
        if expired:
            for fids, _, fut, _, _ in expired:
                self._shed_frame(fut, int(fids.shape[0]))
            frames = [f for f in frames if not (f[4] and now_mono >= f[4])]
            if not frames:
                return
        if len(frames) == 1:
            ids, ns, fut, tid, _ = frames[0]
            await self._dispatch_hashed(ids, ns, fut, tid)
            return
        rec = tracing.RECORDER
        tid = next((f[3] for f in frames if f[3]), 0)
        t_r0 = tracing.now() if rec is not None else 0
        ids = np.concatenate([f[0] for f in frames])
        ns = np.concatenate([f[1] for f in frames])
        if rec is not None:
            # "route": window assembly — frame concatenation in arrival
            # order (the mesh composite records its per-slice partition
            # under the same stage at launch).
            rec.record("route", t_r0, tracing.now(), trace_id=tid,
                       batch=int(ids.shape[0]))
        loop = asyncio.get_running_loop()
        win: asyncio.Future = loop.create_future()
        await self._dispatch_hashed(ids, ns, win, tid)
        exc = win.exception()
        if exc is not None:
            for _, _, fut, _, _ in frames:
                if not fut.done():
                    fut.set_exception(exc)
            return
        out = win.result()
        off = 0
        for fids, _, fut, _, _ in frames:
            k = int(fids.shape[0])
            if not fut.done():
                fut.set_result(out.rows(off, k))
            off += k

    # ------------------------------------------------------------- flush

    def _flush_fwd(self) -> None:
        """Dispatch the coalesced forward-lane windows as their OWN
        launch (ADR-019): local-only rows, never merged with the
        client lanes."""
        if not self._pending_fwd:
            return
        frames = self._pending_fwd
        self._pending_fwd = []
        self._pending_fwd_ids = 0
        task = asyncio.ensure_future(self._dispatch_hashed_window(frames))
        self._inflight.add(task)
        task.add_done_callback(self._inflight.discard)

    def _flush(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if (not self._pending and not self._pending_hashed
                and not self._pending_fwd):
            return
        self._queue_depth.set(0)
        rec = tracing.RECORDER
        trace = self._q_trace
        if rec is not None and self._q_t0:
            # "coalesce": the window's residency — first enqueue to
            # flush, in max_batch units across both lanes.
            rec.record("coalesce", self._q_t0, tracing.now(),
                       trace_id=trace,
                       batch=(len(self._pending) + self._pending_hashed_ids
                              + self._pending_fwd_ids))
        self._q_t0 = 0
        self._q_trace = 0
        if self._pending:
            batch = self._pending
            self._pending = []
            task = asyncio.ensure_future(self._dispatch(batch, trace))
            self._inflight.add(task)
            task.add_done_callback(self._inflight.discard)
        if self._pending_hashed:
            frames = self._pending_hashed
            self._pending_hashed = []
            self._pending_hashed_ids = 0
            task = asyncio.ensure_future(self._dispatch_hashed_window(frames))
            self._inflight.add(task)
            task.add_done_callback(self._inflight.discard)
        self._flush_fwd()

    def _launch_work(self, keys, ns, trace_id=0, t_q=0):
        """Launch stage (runs on the launch executor thread): acquire an
        in-flight slot — blocking HERE is the pipeline's backpressure,
        it stalls later launches, never the event loop — then stage +
        enqueue without waiting on the device."""
        self._window.acquire()
        rec = tracing.RECORDER
        tq0 = tracing.now() if rec is not None else 0
        t0 = time.perf_counter()
        if rec is not None:
            # See _launch_hashed_work: forwarded-fragment linkage.
            tracing.set_current(trace_id)
        try:
            ticket = self.limiter.launch_batch(keys, ns)
        except BaseException:
            self._window.release()
            raise
        finally:
            if rec is not None:
                tracing.set_current(0)
        self._launch_hist.observe(time.perf_counter() - t0)
        if rec is not None:
            if t_q:
                rec.record("queue", t_q, tq0, trace_id=trace_id,
                           batch=len(keys))
            rec.record("launch", tq0, tracing.now(), trace_id=trace_id,
                       batch=len(keys))
        ticket.trace_id = trace_id
        self._depth_add(1)
        return ticket

    def _resolve_target(self, loop, ticket):
        """Schedule one ticket's resolve on the right executor: plain
        tickets keep the FIFO resolve thread; fleet tickets (remote
        forward legs pending — ``ticket.jobs``) move to the side pool
        and release their window slot NOW (see _fleet_pool above)."""
        if getattr(ticket, "jobs", None):
            if self._fleet_pool is None:
                self._fleet_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=16, thread_name_prefix="rl-fleet-merge")
            self._window.release()
            self._depth_add(-1)
            return loop.run_in_executor(
                self._fleet_pool,
                lambda: self._resolve_work(ticket, release=False))
        return loop.run_in_executor(self._resolve_pool,
                                    self._resolve_work, ticket)

    def _resolve_work(self, ticket, release: bool = True):
        rec = tracing.RECORDER
        tn0 = tracing.now() if rec is not None else 0
        t0 = time.perf_counter()
        try:
            out = self.limiter.resolve(ticket)
            if rec is not None:
                tn1 = tracing.now()
                tid = getattr(ticket, "trace_id", 0)
                # "device": the blocking wait on the oldest in-flight
                # dispatch (for a mesh composite this span ENCLOSES its
                # barrier + per-slice spans — the span tree the oracle
                # test walks); "resolve": the host bookkeeping tail.
                rec.record("device", tn0, tn1, trace_id=tid,
                           batch=len(out),
                           outcome=tracing.FAIL_OPEN if out.fail_open
                           else tracing.OK)
                rec.record("resolve", tn1, tracing.now(), trace_id=tid,
                           batch=len(out))
            return out
        except Exception:
            if rec is not None:
                rec.record("device", tn0, tracing.now(),
                           trace_id=getattr(ticket, "trace_id", 0),
                           outcome=tracing.ERROR)
            raise
        finally:
            if release:
                self._window.release()
                self._depth_add(-1)
            self._resolve_hist.observe(time.perf_counter() - t0)

    async def _dispatch(self, batch, trace_id: int = 0) -> None:
        # Deadline shedding at the dispatch boundary (ADR-015): entries
        # whose propagated deadline expired while coalescing are
        # answered per policy here and excluded from the device batch.
        now_mono = time.monotonic()
        expired = [e for e in batch if e[3] and now_mono >= e[3]]
        if expired:
            for _, _, fut, _ in expired:
                self._shed_scalar(fut)
            batch = [e for e in batch if not (e[3] and now_mono >= e[3])]
            if not batch:
                return
        keys = [k for k, _, _, _ in batch]
        ns = [n for _, n, _, _ in batch]
        self._dispatch_batch.observe(float(len(batch)))
        loop = asyncio.get_running_loop()
        t_q = tracing.now() if tracing.RECORDER is not None else 0
        t_tap = (self.limiter.clock.now() if audit.AUDITOR is not None
                 else 0.0)
        ticket = None
        t0 = time.perf_counter()
        if self._pipelined:
            # Launch/resolve split (ADR-010): the launch executor stages
            # and enqueues batch k+1 while the resolve executor blocks on
            # batch k — the device always has work queued.
            try:
                ticket = await loop.run_in_executor(
                    self._pool, self._launch_work, keys, ns, trace_id, t_q)
            except Exception as exc:
                for _, _, fut, _ in batch:
                    if not fut.done():
                        fut.set_exception(exc)
                return
            work = self._resolve_target(loop, ticket)
        else:
            work = loop.run_in_executor(
                self._pool, lambda: self._allow_work(keys, ns, trace_id))
        timed_out = False
        try:
            if self.dispatch_timeout is not None:
                out = await asyncio.wait_for(
                    asyncio.shield(work), self.dispatch_timeout)
            else:
                out = await work
        except asyncio.TimeoutError:
            timed_out = True
        except Exception as exc:
            # Fail-open dispatch failures never get here (the limiter maps
            # them to a fail-open BatchResult); this is fail-closed or a
            # validation race — every waiter gets the error.
            for _, _, fut, _ in batch:
                if not fut.done():
                    fut.set_exception(exc)
            return
        finally:
            self._dispatch_latency.observe(time.perf_counter() - t0)

        if timed_out:
            # SLO breach (ADR-002 at the dispatch layer). The shielded
            # device call keeps running so state converges; waiters are
            # answered NOW by policy.
            self._slo_breaches.inc()
            self._slo_breach_decisions.inc(len(batch))
            cfg = self.limiter.config
            if cfg.fail_open:
                reset_at = self.limiter.clock.now() + float(cfg.window)
                for _, _, fut, _ in batch:
                    if not fut.done():
                        fut.set_result(fail_open_result(cfg.limit, reset_at))
                self.decisions_total += len(batch)
            else:
                err = StorageUnavailableError(
                    f"dispatch exceeded SLO ({self.dispatch_timeout * 1e3:.1f} ms)")
                for _, _, fut, _ in batch:
                    if not fut.done():
                        fut.set_exception(err)
            # The shielded call still consumes the frame's sketch mass:
            # mirror its eventual result into the audit tap so shadow
            # timelines stay whole (ADR-016); also keeps the un-awaited
            # error from leaking.
            t_dec = getattr(ticket, "t_sec", 0.0) or t_tap

            def _late_tap(f, _keys=keys, _ns=ns, _t=t_dec):
                if f.exception() is not None:
                    return
                aud = audit.AUDITOR
                if aud is not None:
                    aud.offer_keys(_keys, _ns, _t, f.result())

            work.add_done_callback(_late_tap)
            return

        self.decisions_total += len(batch)
        # Live accuracy tap (ADR-016): string-lane frames mirror BEFORE
        # the per-request split (the worker hashes with the limiter's
        # prefix rule), stamped with the launch-time now; audit-off is
        # one None check.
        aud = audit.AUDITOR
        if aud is not None:
            aud.offer_keys(keys, ns,
                           getattr(ticket, "t_sec", 0.0) or t_tap, out)
        for i, (_, _, fut, _) in enumerate(batch):
            if not fut.done():
                fut.set_result(out.result(i))

    # ----------------------------------------------------------- control

    async def drain(self) -> None:
        """Flush what is queued and wait for every in-flight dispatch —
        the graceful-shutdown half the reference stubs
        (``cmd/server/main.go:17``)."""
        self._draining = True
        self._flush()
        while self._inflight:
            tasks = list(self._inflight)
            await asyncio.gather(*tasks, return_exceptions=True)
            # Remove directly: awaiting an already-done task does not yield
            # to the loop, so the done-callback discard may not have run
            # yet and the while would otherwise busy-spin.
            self._inflight.difference_update(tasks)

    def close(self) -> None:
        self._pool.shutdown(wait=True)
        if self._resolve_pool is not None:
            self._resolve_pool.shutdown(wait=True)
        if self._fleet_pool is not None:
            self._fleet_pool.shutdown(wait=True)
