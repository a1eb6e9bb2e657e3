"""The hashed, pipelined dispatch surface of the device backends.

What the serving doors and ``_prewarm`` call on a device-backed limiter —
``allow_hashed`` / ``launch_hashed`` (finalized 64-bit key hashes),
``launch_ids`` / ``allow_ids`` (raw u64 ids the step finalizes itself),
``launch_batch`` (string keys, hashed on the host), ``resolve`` — and the
one mechanism under them: a reusable staging slot ``[ids | n | now_us]``
placed by ONE ``device_put``, ONE jitted step that returns its state and
one packed int32 result buffer, a ``DispatchTicket``, ONE fetch at
resolve, ONE native pass that rebuilds the reply's columns from it
(``_unpack``; the format's NumPy twin where nothing can be built), and
the always-on counts of all three (``result_fetches``,
``result_native_unpacks``, ``override_lookup_dispatches``).

The sketch family (algorithms/sketch.py) and the dense backend
(algorithms/dense.py) are both built on it; neither copies it. A limiter
that mixes this in provides:

``_lock``, ``_state``, ``_device``, ``_window_us``, ``_injected_failure``,
``_policy_table``              its own state (``_note_resident()`` once
                               ``_state`` is built);
``_step`` / ``_get_ids_step()``  the compiled steps (finalized / premix);
``_gate_locked(b, now_us)``    what must happen under the lock before a
                               step is enqueued (a rollover, a reclaim);
                               a BatchResult returned here answers the
                               batch without a dispatch;
``_step_args(slot, padded)``   the step's operands;
``_policy_limits(h64)``        per-row override limits for the result;
``_result_format()``           ``(rows, unpack)`` of its packed buffer,
                               ``_tail_words(padded)`` its tail words;
``_note_mass_locked``, ``_note_tail_locked``   what resolve reports back.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

from ratelimiter_tpu import native
from ratelimiter_tpu.core.clock import to_micros
from ratelimiter_tpu.core.errors import StorageUnavailableError
from ratelimiter_tpu.core.types import (
    BatchResult,
    DispatchTicket,
    Result,
    batch_fail_open,
    wire_pack,
)
from ratelimiter_tpu.observability import tracing

_MIN_PAD = 8


def _pad_size(n: int) -> int:
    """Next power of two >= n (>= _MIN_PAD): bounds the number of distinct
    batch shapes XLA compiles (first compile is slow; shapes are cached)."""
    size = _MIN_PAD
    while size < n:
        size *= 2
    return size


def fetch_count(buf) -> int:
    """Device buffers a fetch of the array ``buf`` asks the device for:
    one per addressable shard."""
    return len(buf.sharding.addressable_devices)


def resident_bytes(state) -> dict:
    """``{device: bytes}`` of a state's array leaves, from their shapes,
    dtypes and shardings alone (one shard's bytes on every device that
    holds one: a replicated leaf counts once a device) — no buffer is
    read, so it costs nothing and never waits for the device."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(state):
        shard = int(np.prod(leaf.sharding.shard_shape(leaf.shape),
                            dtype=np.int64)) * leaf.dtype.itemsize
        for dev in leaf.sharding.device_set:
            out[dev] = out.get(dev, 0) + shard
    return out


class HashedLane:
    """Mixin: see the module docstring."""

    #: Names this backend in the errors a failed dispatch raises.
    _lane_name = "sketch"
    #: Whether ``type(self)(config, clock=clock)`` rebuilds an equal
    #: limiter (the native door clones its dispatch shards that way).
    state_from_config = True

    # ------------------------------------------------------------ hashing

    def _hash(self, keys: List[str]) -> np.ndarray:
        # Shared rule (ops/hashing.hash_prefixed_u64): prefix-namespace
        # then bulk-hash — the audit tap's string lane applies the SAME
        # function, so audited keys always match their serving hashes.
        from ratelimiter_tpu.ops.hashing import hash_prefixed_u64

        return hash_prefixed_u64(keys, self.config.prefix)

    # ------------------------------------------------------------ dispatch
    #
    # The hot path is split into a *launch* phase (stage into reusable
    # padded buffers, enqueue the jitted step, return a DispatchTicket
    # without blocking) and a *resolve* phase (block on the device
    # result, one bulk fetch, assemble the BatchResult). Sequential
    # semantics across in-flight tickets are carried by state threading:
    # each launch consumes the previous launch's donated state buffers,
    # so the device executes steps in launch order regardless of when
    # (or on which thread) each ticket is resolved. The synchronous API
    # (allow_hashed / allow_batch) is launch+resolve back to back, so
    # both paths are decision-for-decision identical (ADR-010).

    def _padded_size(self, b: int) -> int:
        """Device batch size for b requests; subclasses align to mesh shape."""
        return _pad_size(b)

    def _place_replicated(self, arr: np.ndarray):
        """Explicit placement for inputs of replicated (non-sharded)
        computations: the pinned slice's own device, else the default."""
        import jax

        return jax.device_put(arr, self._device)

    def _stage_operands(self, buf: np.ndarray, padded: int) -> tuple:
        """The step's batch operands from a filled staging buffer: ONE
        explicit host->device transfer of the whole buffer (the step
        slices it, sketch_kernels.unstage). The replicated mesh's
        placement overrides this with the slot's three views, its
        timestamp replicated (_MeshPlacement._stage_operands); the
        collective router shards ONE buffer by rows instead, the scalars
        repeated in each device's row (CollectiveMeshLimiter
        ._acquire_slot)."""
        return (self._place_replicated(buf),)

    def _init_staging(self) -> None:
        # Reusable staging buffers per padded-size bucket: a launch pops
        # a free slot (allocating only when every slot is in flight —
        # bounded by the door's in-flight window) and resolve returns it
        # AFTER the device has consumed the transfer. Eliminates the
        # per-dispatch np.zeros allocations the pre-pipeline hot path
        # paid (ISSUE-3 tentpole item 2).
        self._staging: dict = {}
        self._staging_lock = threading.Lock()
        # Offered mass of launched-but-unresolved tickets: the strict
        # overload gate counts it AS IF fully admitted (see
        # SketchLimiter._over_budget_locked) so a deep in-flight window
        # cannot slip inflight*max_batch of admissions past the accuracy
        # budget — pessimism errs toward denying, strict mode's direction.
        self._inflight_mass = 0
        # Device buffers resolve has fetched (result_fetches).
        self._fetches = 0
        # Dispatches launched while the override table held an entry
        # (override_lookup_dispatches).
        self._override_lookups = 0
        # The native rebuild of this lane's result format — loaded (built,
        # on a checkout's first start) HERE, where the lane is built, so
        # that prewarm runs it and no served dispatch waits for g++; None
        # on a host that cannot build it (the NumPy twin serves) — and
        # the resolves it served (result_native_unpacks).
        self._native_unpack = native.column_unpacker(self._result_format()[1])
        self._native_unpacks = 0

    def _note_resident(self) -> None:
        """Reckon what ``state_resident_bytes`` reports. Called where the
        state is BUILT (the constructors, a mesh placement, a window
        migration that changes the ring) with the leaves at hand — never
        at scrape, when the leaves may be buffers a launch has donated."""
        self._resident = resident_bytes(self._state)

    def state_resident_bytes(self) -> dict:
        """``{jax.Device: bytes}`` of limiter state resident on each
        device: the ``nbytes`` of the state leaves the decision step is
        handed, as reckoned when the state was built. Exported at scrape
        as ``rate_limiter_state_resident_bytes``."""
        return dict(self._resident)

    def memory_bytes(self) -> int:
        """Device memory the limiter's state holds, every device summed
        (a mesh's replicas each count) — constant in key cardinality on
        the sketches, 32 B an entry on the dense token bucket."""
        return sum(self._resident.values())

    def _acquire_staging(self, padded: int) -> np.ndarray:
        with self._staging_lock:
            free = self._staging.get(padded)
            if free:
                return free.pop()
        # A slot is ONE uint64 buffer [ids(P) | n(P) | now_us(1)]: one
        # transfer carries every per-dispatch operand (a transfer's cost
        # is per call, not per byte, at these sizes — PERF.md §6, PR 26),
        # and the hashed wire lane stays a single memcpy into its head.
        return np.empty(2 * padded + 1, dtype=np.uint64)

    def _release_staging(self, padded: int, slot) -> None:
        if slot is None:
            return
        with self._staging_lock:
            self._staging.setdefault(padded, []).append(slot)

    def _gate_locked(self, b: int, now_us: int) -> Optional[BatchResult]:
        """Under the lock, before the step is enqueued. A BatchResult
        answers the batch here, with no state write and no dispatch."""
        return None

    def _launch_hashed(self, h64: np.ndarray, ns: np.ndarray,
                       now_us: int, t_sec: float, *, premix: bool = False,
                       wire: bool = False) -> DispatchTicket:
        b = h64.shape[0]
        # The dispatch stage from inside (ADR-014 addendum): prep ->
        # place -> step -> finish, back to back. Tracing off, sp is the
        # shared no-op and next() does nothing. A dispatch is ONE
        # transfer (place) and ONE program launch (step): the step
        # slices the slot and packs its own verdicts, so "finish" is
        # host bookkeeping only (ADR-010 addenda).
        with tracing.span("prep", batch=b) as sp:
            padded = self._padded_size(b)
            slot = self._acquire_staging(padded)
            slot[:b] = h64
            slot[b:padded] = 0
            # n and now_us are signed: written through an int64 view of
            # the same bytes, narrowed back on device.
            tail = slot.view(np.int64)
            tail[padded:padded + b] = ns
            tail[padded + b:2 * padded] = 0
            tail[2 * padded] = now_us
            launched = False
            try:
                with self._lock:
                    if self._injected_failure is not None:
                        raise self._injected_failure
                    answered = self._gate_locked(b, now_us)
                    if answered is not None:
                        return DispatchTicket(result=answered)
                    step = self._get_ids_step() if premix else self._step
                    window_us = self._window_us
                    sp.next("place")
                    args = self._step_args(slot, padded)
                    sp.next("step")
                    self._state, outs = step(*args)
                    self._fence_dispatch(outs)
                    sp.next("finish")
                    # Inside the lock: a concurrent set/delete_override
                    # rebuilds the table's sorted views, and a torn read
                    # would mis-index. Only a table with an entry sent
                    # the step through its lookup (counted), and only
                    # then are per-row limits assembled host-side (the
                    # common empty-table case stays hash-free here).
                    limits = None
                    if len(self._policy_table):
                        self._override_lookups += 1
                        if premix:
                            from ratelimiter_tpu.ops.hashing import splitmix64

                            limits = self._policy_limits(splitmix64(h64))
                        else:
                            limits = self._policy_limits(h64)
                    offered = int(ns.sum())
                    self._inflight_mass += offered
                launched = True
            finally:
                # Any non-launch exit (injected failure, an answer at the
                # gate, a failing step/rollover) must return the slot to
                # the pool — only a ticket-owned slot is recycled by
                # _retire_ticket.
                if not launched:
                    self._release_staging(padded, slot)
            t = DispatchTicket()
            # The step's own output, ONE int32 buffer: resolve makes one
            # fetch and rebuilds the 64-bit and float columns in NumPy
            # from it, now_us and the window the step was built for. A
            # wire-lane ticket (the asyncio door's hashed lane) has its
            # reply buffers packed there too (core/types.wire_pack).
            t.outs = outs
            t.wire = wire
            t.window_us = window_us
            t.b = b
            t.limit = self.config.limit
            t.limits = limits
            t.ns = np.asarray(ns)
            t.offered = offered
            t.now_us = now_us
            t.t_sec = t_sec
            t.slot = slot
            t.padded = padded
        # Where "prep" opened and "finish" closed: a door that stamps
        # its own callback records what lies around them.
        t.t_lane = (sp.t_open, sp.t_close)
        return t

    def _fence_dispatch(self, outs) -> None:
        """Complete a just-launched step before the dispatch lock drops.

        No-op on the single-chip path, where in-flight executions are
        independent and the async dispatch stream is the pipelining win.
        Mesh backends override: their step embeds a per-chip collective,
        and on the CPU host platform concurrent in-flight rendezvous
        starve the shared device pool into a permanent deadlock (see
        _MeshPlacement._fence_dispatch)."""

    def _note_mass_locked(self, admitted: int, now_us: int) -> None:
        """Resolve's report of a ticket's admitted mass (lock held)."""

    def _tail_words(self, padded: int) -> int:
        """Tail words of the packed result of a step over ``padded``
        rows (after the rows): what the step was built with."""
        return 0

    def _note_tail_locked(self, t: DispatchTicket, tails) -> None:
        """Resolve's report of the result buffer's tail words, one row a
        shard (lock held)."""

    def _retire_ticket(self, t: DispatchTicket, admitted: int,
                       fetched: int = 0, tails=None,
                       native: bool = False) -> None:
        """Once per launched ticket (t.slot is the sentinel): recycle the
        staging buffers — the step consumed the transfer once its result
        is ready (or failed) — and, in ONE lock acquisition, swap the
        ticket's offered mass out of the strict gate's in-flight
        pessimism for its actual admitted mass. A two-step swap would
        open a window where the batch counts as neither, letting a
        concurrent launch slip past the budget. ``fetched`` device
        buffers and a ``native`` rebuild of the columns join the
        always-on counts under the same lock, and the result's tail
        words are handed over there too."""
        if t.slot is None:
            return
        self._release_staging(t.padded, t.slot)
        t.slot = None
        with self._lock:
            self._inflight_mass -= t.offered
            self._note_mass_locked(admitted, t.now_us)
            self._fetches += fetched
            self._native_unpacks += native
            if tails is not None:
                self._note_tail_locked(t, tails)

    @property
    def result_fetches(self) -> int:
        """Device buffers resolve has asked the device for, one per array
        leaf per addressable shard (cumulative, always on):
        ``rate_limiter_result_fetches_total``. One a dispatch since the
        step packs its result; a four-column result was four (seven
        underneath on a TPU, a 64-bit array being two buffers)."""
        return self._fetches

    @property
    def result_native_unpacks(self) -> int:
        """Resolves whose columns the native pass built (cumulative,
        always on): ``rate_limiter_result_native_unpacks_total``. One a
        dispatch where the extension is loaded; 0 on a host that serves
        from the NumPy twin."""
        return self._native_unpacks

    @property
    def override_lookup_dispatches(self) -> int:
        """Dispatches launched while the override table held an entry,
        i.e. whose step ran the per-row lookup and not the branch that
        skips it (policy_kernels.limit_for_rows) — cumulative, always on,
        counted under the launch's own lock:
        ``rate_limiter_override_lookup_dispatches_total``. The host's
        count of entries, not the device's predicate: they differ only
        for a table whose one entry packs to exactly PAD_KEY."""
        return self._override_lookups

    def _unpack(self, words: np.ndarray, t: DispatchTicket,
                shards: int = 1, tail: int = 0) -> tuple:
        """``(BatchResult's four columns, each shard's tail words, the
        admitted mass)`` from the fetched result buffer of ticket ``t``
        — the ONE place a fetch becomes columns. One native call that
        keeps the interpreter (native/hasher.cpp unpack_columns) where
        the extension is loaded; else the format's NumPy twin over
        ``result_rows``, and None for the mass: the caller that wants it
        sums ``ns`` over the allowed rows itself."""
        if self._native_unpack is not None:
            per = words.shape[0] // shards
            cols, admitted = self._native_unpack(
                words, shards, tail, t.b, t.now_us, t.window_us, t.ns)
            return (cols, words.reshape(shards, per)[:, per - tail:],
                    admitted)
        from ratelimiter_tpu.ops import sketch_kernels

        n_rows, twin = self._result_format()
        rows, tails = sketch_kernels.result_rows(words, n_rows,
                                                 shards=shards, tail=tail)
        return twin(rows, t.b, t.now_us, t.window_us), tails, None

    def _resolve_ticket(self, t: DispatchTicket) -> BatchResult:
        if t.result is not None:
            return t.result
        # One shard, or under the replicated mesh placement one a chip
        # (the buffer is sharded like the batch).
        shards = fetch_count(t.outs)
        tail = self._tail_words(t.padded)
        try:
            # block_until_ready releases the GIL while the device drains,
            # so a completer thread resolving batch k never stalls the
            # thread launching batch k+1.
            t.outs.block_until_ready()
            # "fetch": device ready -> np.asarray returned. ONE buffer
            # (a shard per device) comes over in one call that blocks
            # with the GIL released and then waits to get it back;
            # "unpack": the rest, one pass over [:b].
            with tracing.span("fetch", batch=t.b,
                              trace_id=t.trace_id) as sp:
                words = np.asarray(t.outs)
                sp.next("unpack")
                (allowed, remaining, retry, reset_at), tails, admitted = \
                    self._unpack(words, t, shards, tail)
        except BaseException:
            self._retire_ticket(t, 0)
            raise
        wire_packed = None
        if t.wire:
            wire_packed, remaining, retry, reset_at = wire_pack(
                allowed, remaining, retry, reset_at)
        res = BatchResult(
            allowed=allowed,
            limit=t.limit,
            remaining=remaining,
            retry_after=retry,
            reset_at=reset_at,
            limits=t.limits,
            wire_packed=wire_packed,
        )
        native = admitted is not None
        if not native:
            admitted = int(t.ns[allowed].sum())
        self._retire_ticket(t, admitted, fetched=shards,
                            tails=tails if tail else None, native=native)
        t.result = res
        t.outs = None
        return res

    def _dispatch_hashed(self, h64: np.ndarray, ns: np.ndarray,
                         now_us: int, t_sec: float = 0.0) -> BatchResult:
        return self._resolve_ticket(self._launch_hashed(h64, ns, now_us,
                                                        t_sec))

    # ------------------------------------------------ pipelined public API

    pipelined = True

    def _launch_guarded(self, h64: np.ndarray, ns_arr: np.ndarray,
                        t: float, *, premix: bool = False,
                        wire: bool = False) -> DispatchTicket:
        """Shared fail-open/fail-closed contract for the launch entry
        points (mirrors allow_hashed): fail-open configs get a
        pre-resolved fail-open ticket, fail-closed raise at launch."""
        try:
            return self._launch_hashed(h64, ns_arr, to_micros(t), t,
                                       premix=premix, wire=wire)
        except Exception as exc:
            if self.config.fail_open:
                return DispatchTicket(result=batch_fail_open(
                    h64.shape[0], self.config.limit,
                    t + float(self.config.window)))
            raise StorageUnavailableError(
                f"{self._lane_name} launch failed: {exc}") from exc

    def launch_hashed(self, h64: np.ndarray,
                      ns: Optional[np.ndarray] = None, *,
                      now: Optional[float] = None) -> DispatchTicket:
        """Launch phase of the pipelined hot path: stage pre-hashed keys,
        enqueue the jitted step, and return a ticket WITHOUT blocking on
        the device. Like allow_hashed, ns is trusted (the serving tier
        validated at the wire)."""
        self._check_open()
        h64 = np.asarray(h64, dtype=np.uint64)
        if ns is None:
            ns_arr = np.ones(h64.shape[0], dtype=np.int64)
        else:
            ns_arr = np.asarray(ns, dtype=np.int64)
        t = self.clock.now() if now is None else float(now)
        return self._launch_guarded(h64, ns_arr, t)

    def launch_ids(self, ids: np.ndarray,
                   ns: Optional[np.ndarray] = None, *,
                   now: Optional[float] = None,
                   wire: bool = False) -> DispatchTicket:
        """Raw-u64-id launch (the T_ALLOW_HASHED wire lane, ADR-011):
        ids are tenant/key identifiers, NOT finalized hashes — the
        splitmix64 finalizer (and the sketches' (h1, h2) split) runs
        inside the jitted step, so the host's per-key work is one
        staging memcpy. The id keyspace is disjoint from the string-key
        space (different finalization); reset/policy control surfaces
        address string keys only. ``wire=True`` has resolve also pack
        the reply's wire buffers (core/types.wire_pack) for the
        zero-copy responder path."""
        self._check_open()
        ids = np.asarray(ids, dtype=np.uint64)
        if ns is None:
            ns_arr = np.ones(ids.shape[0], dtype=np.int64)
        else:
            ns_arr = np.asarray(ns, dtype=np.int64)
        t = self.clock.now() if now is None else float(now)
        return self._launch_guarded(ids, ns_arr, t, premix=True, wire=wire)

    def allow_ids(self, ids: np.ndarray,
                  ns: Optional[np.ndarray] = None, *,
                  now: Optional[float] = None) -> BatchResult:
        """Synchronous raw-u64-id decide: launch_ids + resolve."""
        return self.resolve(self.launch_ids(ids, ns, now=now))

    def launch_batch(self, keys: List[str],
                     ns: Optional[np.ndarray] = None, *,
                     now: Optional[float] = None) -> DispatchTicket:
        """String-key launch: validate + hash host-side, then the hashed
        launch path (the asyncio door's pipelined entry point)."""
        self._check_open()
        from ratelimiter_tpu.algorithms.base import check_key, check_n

        keys = list(keys)
        for k in keys:
            check_key(k)
        if ns is None:
            ns_arr = np.ones(len(keys), dtype=np.int64)
        else:
            for n in ns:
                check_n(int(n))
            ns_arr = np.asarray(ns, dtype=np.int64)
        t = self.clock.now() if now is None else float(now)
        return self._launch_guarded(self._hash(keys), ns_arr, t)

    def resolve(self, ticket: DispatchTicket) -> BatchResult:
        """Resolve phase: block on the launched dispatch and assemble its
        BatchResult (idempotent — a resolved ticket returns its cached
        result). Device errors surfacing at the fetch honor the same
        fail-open/fail-closed contract as the synchronous path."""
        try:
            return self._resolve_ticket(ticket)
        except Exception as exc:
            if self.config.fail_open:
                res = batch_fail_open(ticket.b, self.config.limit,
                                      ticket.t_sec
                                      + float(self.config.window))
                ticket.result = res
                ticket.outs = None
                return res
            raise StorageUnavailableError(
                f"{self._lane_name} dispatch failed: {exc}") from exc

    def allow_hashed(self, h64: np.ndarray, ns: Optional[np.ndarray] = None,
                     *, now: Optional[float] = None) -> BatchResult:
        """Fast path: decide a batch of pre-hashed uint64 keys. This is the
        interface the serving tier and benchmarks use — host string handling
        is out of the hot loop (SURVEY.md §7.4.4). Launch + resolve back to
        back; the pipelined doors split the two phases (ADR-010)."""
        self._check_open()
        h64 = np.asarray(h64, dtype=np.uint64)
        if ns is None:
            ns_arr = np.ones(h64.shape[0], dtype=np.int64)
        else:
            ns_arr = np.asarray(ns, dtype=np.int64)
        t = self.clock.now() if now is None else float(now)
        try:
            return self._dispatch_hashed(h64, ns_arr, to_micros(t), t)
        except Exception as exc:
            if self.config.fail_open:
                return batch_fail_open(h64.shape[0], self.config.limit,
                                       t + float(self.config.window))
            raise StorageUnavailableError(
                f"{self._lane_name} dispatch failed: {exc}") from exc

    def _allow_batch(self, keys: list, ns: np.ndarray, now: float) -> BatchResult:
        try:
            return self._dispatch_hashed(self._hash(keys), ns, to_micros(now),
                                         now)
        except Exception as exc:
            if self.config.fail_open:
                return batch_fail_open(len(keys), self.config.limit,
                                       now + float(self.config.window))
            raise StorageUnavailableError(
                f"{self._lane_name} dispatch failed: {exc}") from exc

    def _allow_n(self, key: str, n: int, now: float) -> Result:
        return self._allow_batch([key], np.array([n], dtype=np.int64), now).result(0)

    # ---------------------------------------------------- fault injection

    def inject_failure(self, exc: Optional[Exception] = None) -> None:
        """Test hook: make every subsequent dispatch fail (the analog of
        miniredis ``mr.Close()`` mid-test, SURVEY.md §4.2.3). Pass None
        for a generic failure; ``heal()`` ends it."""
        self._injected_failure = exc if exc is not None else RuntimeError(
            "injected backend failure")

    def heal(self) -> None:
        self._injected_failure = None
