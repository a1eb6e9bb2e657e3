"""Collective mesh router — one SPMD dispatch per frame (ADR-024).

``CollectiveMeshLimiter`` is the ``MeshSpec.router="collective"`` twin
of the host-routed ``SlicedMeshLimiter`` (ADR-013). State placement is
IDENTICAL — one independent, device-pinned single-chip limiter per
device, every key owned by ``h64 % n`` — but a frame is dispatched as
ONE jitted shard_map step over the slice mesh
(ops/route_kernels.build_routed_step): each device takes an even 1/n
shard of the frame columns, computes owners on device, all-to-all's
rows to their owning slices, runs the unchanged fused decision kernel
against its own slice state, and all-to-all's the verdicts back to
frame order. The host stages ONE buffer — a row a device: its shard of
the two columns, the timestamp and the row count (_fill_column) — places
it with one sharded device_put and fetches one packed buffer (a shard a
device); it never argsorts, never builds index maps, never fans out
sub-launches, and resolve blocks on ONE ticket.

Because the per-slice states stay exactly where the host router keeps
them (``self.slices[i]._state``, assembled zero-copy into a global
sharded array per launch and written back shard-by-shard), everything
else — control plane, policy overrides, hierarchy cascade,
capture/restore (including cross-slice-count re-bucketing), chaos
injection, stats — is inherited from SlicedMeshLimiter unchanged, and
decisions are bit-identical to the host-routed oracle
(tests/test_collective_router.py pins it).

Escape hatches back to the host router (never silent):

* bin overflow — a frame whose per-(source, destination) row count
  exceeds the static bin capacity sets a device-computed flag; the step
  leaves state untouched and resolve re-dispatches the ORIGINAL frame
  through the inherited host router (each row admitted exactly once);
* strict overload policy — the windowed sketch's strict gate is a
  per-slice host-side admission decision that must see each slice's
  offered mass BEFORE dispatch; collective frames route host-side when
  it is enabled;
* quarantine is REFUSED at config validation: a collective dispatch has
  whole-mesh blast radius, so per-slice failure domains cannot hold
  (docs/ADR/024, "blast radius").
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from ratelimiter_tpu.algorithms.hashed_lane import _pad_size, fetch_count
from ratelimiter_tpu.core.clock import Clock, to_micros
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.core.errors import StorageUnavailableError
from ratelimiter_tpu.core.types import (
    BatchResult,
    DispatchTicket,
    batch_fail_open,
    wire_pack,
)
from ratelimiter_tpu.observability import tracing
from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter


class CollectiveDispatchTicket(DispatchTicket):
    """Ticket for one collective frame dispatch.

    ``outs`` holds the device-side result: ONE int32 buffer sharded over
    the mesh, each device's shard the rule's packed rows of its frame
    rows and a tail of that slice's admitted mass and the overflow flag
    (ops/route_kernels.build_routed_step). The original frame columns
    ride along so the overflow fallback can re-dispatch through the host
    router with the ORIGINAL decision timestamp — never from ``slot``,
    the pooled staging buffer the ticket owns until resolve gives it
    back (``padded`` is its L)."""

    __slots__ = ("arrays", "premix", "wire_lane")

    def __init__(self, result=None):
        super().__init__(result)
        self.arrays = None
        self.premix = False
        self.wire_lane = False


def _fill_column(dst: np.ndarray, src: np.ndarray) -> None:
    """Frame column ``src`` into ``dst``, the (n, L) view of one column of
    the staging slot: row c takes frame rows c*L .. c*L + L - 1 (global
    row order unchanged), what is left of the slot is zeroed — pad rows
    (key 0, n = 0) are decision-inert."""
    L = dst.shape[1]
    full, rest = divmod(src.shape[0], L)
    dst[:full] = src[:full * L].reshape(full, L)
    if full < dst.shape[0]:
        dst[full, :rest] = src[full * L:]
        dst[full, rest:] = 0
        dst[full + 1:] = 0


class CollectiveMeshLimiter(SlicedMeshLimiter):
    """Sliced mesh limiter whose decide path is one collective dispatch
    (``MeshSpec.router="collective"``, ADR-024)."""

    def __init__(self, config: Config, clock: Optional[Clock] = None, *,
                 n_devices: Optional[int] = None,
                 devices: Optional[Sequence] = None):
        super().__init__(config, clock, n_devices=n_devices,
                         devices=devices)
        if self.quarantine is not None:  # pragma: no cover - config gate
            from ratelimiter_tpu.core.errors import InvalidConfigError

            raise InvalidConfigError(
                "router='collective' cannot wrap slices in quarantine "
                "guards (whole-mesh blast radius; MeshSpec.validate "
                "refuses this combination)")
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.mesh = Mesh(np.asarray([s._device for s in self.slices]),
                         ("chips",))
        #: How the staged frame is placed: a row of the slot a device.
        self._frame_sharding = NamedSharding(self.mesh, P("chips"))
        # Reusable staging slots per L, as on the one-chip lane
        # (HashedLane._acquire_staging): a launch pops a free one, the
        # ticket owns it, resolve gives it back once the step has
        # consumed the transfer — on every exit.
        self._slots: dict = {}
        self._slots_lock = threading.Lock()
        from ratelimiter_tpu.ops import route_kernels

        _, self._mut_keys, self._ro_keys = route_kernels.state_layout(
            self.config)
        #: Serializes collective dispatches: the step is one mesh-wide
        #: execution, and the per-slice state assembly/writeback must be
        #: atomic against control-plane and capture paths (which take
        #: the per-slice locks this launch also holds, in slice order).
        self._mesh_lock = threading.Lock()
        self._ro_cache: dict = {}
        self._pol_dev = None
        self._pol_ver = -1
        self._hier_dev_mesh = None
        self._hier_ver = -1
        #: Frames launched as one mesh-wide program, and frames handed to
        #: the host router instead, by reason: the one source router_stats()
        #: and /metrics (MetricsDecorator's collect hook) both read. A
        #: launch counts under _mesh_lock; an overflow is found on the
        #: resolving thread, so the fallbacks have a lock of their own.
        self.dispatches = 0
        #: Operand shards the routed launches sent to the devices, one
        #: per array operand per addressable shard (a host scalar handed
        #: to the jitted call would count as the n shards jit makes of
        #: it): n_slices a dispatch, the one staged slot. Counted beside
        #: ``dispatches``.
        self.placements = 0
        self._fallbacks = {"overflow": 0, "strict": 0}
        #: Device buffers this router's own resolve has fetched, and the
        #: resolves whose columns the native pass built (the slices count
        #: their own; result_fetches / result_native_unpacks sum both).
        self._fetches = 0
        self._native_unpacks = 0
        #: Frames this router launched while the override table held an
        #: entry (the slices count their own dispatches).
        self._override_lookups = 0
        self._stats_lock = threading.Lock()
        self._strict_gate = bool(getattr(self.slices[0], "_strict", False))
        self._cpu = self.mesh.devices.flat[0].platform == "cpu"

    # ----------------------------------------------------- table operands

    def _policy_mesh(self):
        """Mesh-replicated device copy of the override table (slices are
        write-all identical — slice 0 is canonical). Slice locks held."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = self.slices[0]._policy_table
        if self._pol_dev is None or self._pol_ver != t.version:
            host = t.host_arrays()
            sh = NamedSharding(self.mesh, P())
            self._pol_dev = {"key": jax.device_put(host["key"], sh),
                             "limit": jax.device_put(host["limit"], sh)}
            self._pol_ver = t.version
        return self._pol_dev

    def _hier_mesh(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = self.slices[0]._hier_table
        if t is None:
            return None
        if self._hier_dev_mesh is None or self._hier_ver != t.version:
            host = t.host_arrays()
            sh = NamedSharding(self.mesh, P())
            self._hier_dev_mesh = {k: jax.device_put(v, sh)
                                   for k, v in host.items()}
            self._hier_ver = t.version
        return self._hier_dev_mesh

    # ----------------------------------------------------- state assembly

    def _assemble_leaf(self, k: str, *, cache: bool):
        """Zero-copy global view over the slices' pinned state buffers
        (scalar leaves stack to (n,)). RO leaves cache on buffer
        identity — invalidated exactly when a rollover/restore/reset
        installs new arrays."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        parts = [s._state[k] for s in self.slices]
        ids = tuple(id(p) for p in parts)
        if cache:
            hit = self._ro_cache.get(k)
            if hit is not None and hit[0] == ids:
                return hit[1]
        if parts[0].ndim == 0:
            parts = [p.reshape(1) for p in parts]
        lead = parts[0].shape[0]
        gshape = (self.n_slices * lead,) + tuple(parts[0].shape[1:])
        arr = jax.make_array_from_single_device_arrays(
            gshape, NamedSharding(self.mesh, P("chips")), parts)
        if cache:
            self._ro_cache[k] = (ids, arr)
        return arr

    def _assemble_state(self):
        mut = {k: self._assemble_leaf(k, cache=False)
               for k in self._mut_keys}
        ro = {k: self._assemble_leaf(k, cache=True) for k in self._ro_keys}
        return mut, ro

    def _writeback(self, new_mut) -> None:
        """Install each device's output shard as its slice's state leaf
        (matched by device, never by list order)."""
        for k in self._mut_keys:
            shards = {sh.device: sh.data
                      for sh in new_mut[k].addressable_shards}
            for s in self.slices:
                v = shards[s._device]
                if s._state[k].ndim == 0:
                    v = v.reshape(())
                s._state[k] = v

    # --------------------------------------------------- routed dispatch

    def _fell_back(self, reason: str) -> None:
        with self._stats_lock:
            self._fallbacks[reason] += 1

    @property
    def fallbacks(self) -> int:
        """Frames the host router decided in this router's place."""
        return sum(self._fallbacks.values())

    def _use_host_router(self, b: int) -> bool:
        # Strict overload gating is a host-side per-slice admission
        # decision made BEFORE dispatch against each slice's offered
        # mass — it cannot ride a whole-mesh step. Empty frames take
        # the host router's passthrough (nothing to route).
        if b and self._strict_gate:
            self._fell_back("strict")
        return b == 0 or self._strict_gate

    def _acquire_slot(self, L: int) -> np.ndarray:
        with self._slots_lock:
            free = self._slots.get(L)
            if free:
                return free.pop()
        # ONE uint64 buffer, a row a device: [ids(L) | n(L) | now_us | b]
        # — the one-chip slot's layout (HashedLane._acquire_staging) plus
        # the frame's true row count, the two scalars repeated in every
        # row so that the buffer shards by rows and no host scalar reaches
        # the jitted call (sketch_kernels.unstage_routed).
        return np.empty((self.n_slices, 2 * L + 2), dtype=np.uint64)

    def _stage_frame(self, t: DispatchTicket, L: int, arrays: np.ndarray,
                     ns: np.ndarray, now_us: int) -> np.ndarray:
        """A slot of the pool, owned by ``t`` from here on, filled with
        the frame."""
        t.padded = L
        t.slot = slot = self._acquire_slot(L)
        _fill_column(slot[:, :L], arrays)
        # n, now_us and b are signed: written through an int64 view of
        # the same bytes, narrowed back on device.
        tail = slot.view(np.int64)
        _fill_column(tail[:, L:2 * L], ns)
        tail[:, 2 * L:] = (now_us, arrays.shape[0])
        return slot

    def _release_slot(self, t: DispatchTicket) -> None:
        """Give a ticket's slot back, once: the step consumed the
        transfer when its result is ready (or it failed)."""
        if t.slot is None:
            return
        with self._slots_lock:
            self._slots.setdefault(t.padded, []).append(t.slot)
        t.slot = None

    def _launch_routed(self, arrays: np.ndarray, ns: np.ndarray,
                       now: float, *, premix: bool,
                       wire: bool) -> CollectiveDispatchTicket:
        import jax

        from ratelimiter_tpu.ops import route_kernels

        b = int(arrays.shape[0])
        t = CollectiveDispatchTicket()
        # The launch from inside, on the stage names the single-chip
        # launch uses where the work is the same (prep -> place -> step
        # -> finish, algorithms/sketch.py) plus the two stages only this
        # path has: assemble and writeback. "route" spans the whole
        # launch. Tracing off, both are the shared no-op.
        with tracing.span("route", batch=b), \
                tracing.span("prep", batch=b) as sp:
            n = self.n_slices
            now_us = to_micros(now)
            L = _pad_size(max(1, -(-b // n)))
            C = route_kernels.bin_capacity(
                L, n, self.config.mesh.bin_headroom)
            step = route_kernels.build_routed_step(
                self.config, self.mesh, premix=premix, L=L, capacity=C)
            slot = self._stage_frame(t, L, arrays, ns, now_us)
            try:
                # The frame is placed BEFORE the locks: it reads no
                # limiter state, and placement is the longest host stage
                # of the launch (ONE sharded device_put, a row of the
                # slot a device). Held across it, the slice locks were
                # taken for nearly the whole launch, and resolve — which
                # needs each slice's lock for its mass bookkeeping —
                # could finish only between two launches: on the chip the
                # door then fell, within seconds and for good, into a
                # state where dispatcher and completer take turns
                # (PERF.md §6, PR 27).
                sp.next("place")
                staged = jax.device_put(slot, self._frame_sharding)
                sp.next("assemble")
                with self._mesh_lock:
                    for s in self.slices:
                        s._lock.acquire()
                    try:
                        for s in self.slices:
                            if s._injected_failure is not None:
                                raise s._injected_failure
                            s._sync_period(now_us)
                        mut, ro = self._assemble_state()
                        # Every operand is a committed device array with
                        # the sharding the program was built for: the
                        # state views, the staged frame, and the tables —
                        # cached device copies, rebuilt under the slice
                        # locks when an override or tenant changes.
                        args = (mut, ro, staged, self._policy_mesh())
                        hier = self._hier_mesh()
                        if hier is not None:
                            args = args + (hier,)
                        sp.next("step")
                        new_mut, words = step(*args)
                        if self._cpu:
                            # Same rationale as
                            # _MeshPlacement._fence_dispatch: xla:cpu
                            # collective rendezvous starve the shared
                            # device pool under concurrent executions —
                            # cap the stream at one while the dispatch
                            # locks are held.
                            jax.block_until_ready(words)
                        sp.next("writeback")
                        self._writeback(new_mut)
                        self.dispatches += 1
                        self.placements += fetch_count(staged)
                        window_us = self.slices[0]._window_us
                        sp.next("finish")
                        limits = None
                        if len(self.slices[0]._policy_table):
                            self._override_lookups += 1
                            if premix:
                                from ratelimiter_tpu.ops.hashing import (
                                    splitmix64)

                                limits = self.slices[0]._policy_limits(
                                    splitmix64(arrays))
                            else:
                                limits = self.slices[0]._policy_limits(
                                    arrays)
                    finally:
                        for s in reversed(self.slices):
                            s._lock.release()
                t.outs = words
            finally:
                # Any exit without a launched step (an injected failure,
                # a failing placement, step or rollover) gives the slot
                # back here — only a launched ticket keeps it for
                # resolve.
                if t.outs is None:
                    self._release_slot(t)
            t.window_us = window_us
            t.b = b
            t.limit = self.config.limit
            t.limits = limits
            t.ns = np.asarray(ns)
            t.now_us = now_us
            t.t_sec = now
            t.arrays = arrays
            t.premix = premix
            t.wire_lane = bool(wire and premix)
            t.wire = t.wire_lane
        t.t_lane = (sp.t_open, sp.t_close)
        return t

    def _launch_routed_guarded(self, arrays: np.ndarray, ns: np.ndarray,
                               now: float, *, premix: bool,
                               wire: bool) -> DispatchTicket:
        """Same fail-open/fail-closed launch contract as the slices'
        _launch_guarded — but a collective launch failure spans the
        whole mesh, so fail-open covers the entire frame (the blast-
        radius trade documented in ADR-024)."""
        try:
            return self._launch_routed(arrays, ns, now, premix=premix,
                                       wire=wire)
        except Exception as exc:
            if self.config.fail_open:
                return DispatchTicket(result=batch_fail_open(
                    int(arrays.shape[0]), self.config.limit,
                    now + float(self.config.window)))
            raise StorageUnavailableError(
                f"collective launch failed: {exc}") from exc

    def resolve(self, ticket: DispatchTicket) -> BatchResult:
        if not isinstance(ticket, CollectiveDispatchTicket):
            return super().resolve(ticket)
        if ticket.result is not None:
            return ticket.result
        from ratelimiter_tpu.ops import route_kernels, sketch_kernels

        trace_id = getattr(ticket, "trace_id", 0)
        try:
            with tracing.span("barrier", batch=ticket.b, trace_id=trace_id):
                ticket.outs.block_until_ready()
                # "fetch", as on one chip (SketchLimiter._resolve_ticket):
                # device ready -> NumPy columns built, over ONE buffer of
                # a shard a device.
                shards = fetch_count(ticket.outs)
                with tracing.span("fetch", batch=ticket.b,
                                  trace_id=trace_id) as sp:
                    words = np.asarray(ticket.outs)
                    sp.next("unpack")
                    (allowed, remaining, retry, reset_at), tails, mass = \
                        self.slices[0]._unpack(
                            words, ticket, shards,
                            route_kernels.ROUTED_TAIL)
        except Exception as exc:
            ticket.outs = None
            if self.config.fail_open:
                res = batch_fail_open(ticket.b, self.config.limit,
                                      ticket.t_sec
                                      + float(self.config.window))
                ticket.result = res
                return res
            raise StorageUnavailableError(
                f"collective resolve failed: {exc}") from exc
        finally:
            # The result is ready (or the step failed): the transfer of
            # the staged frame is consumed either way, on every exit.
            self._release_slot(ticket)
        with self._stats_lock:
            self._fetches += shards
            # The slices' masses ride in the tails; what _unpack summed
            # only says which pass built the columns.
            self._native_unpacks += mass is not None
        ticket.outs = None
        if tails[:, 2].any():
            # Bin overflow: the step left every state leaf untouched,
            # so re-dispatching the ORIGINAL frame (same rows, same
            # decision timestamp) through the host router admits each
            # row exactly once — no lost, no duplicated mass.
            self._fell_back("overflow")
            arrays = ticket.arrays
            owners = (self.owner_of_id(arrays) if ticket.premix
                      else self.owner_of_hash(arrays))
            sub = self._launch_split(arrays, ticket.ns, owners,
                                     ticket.t_sec, premix=ticket.premix,
                                     wire=ticket.wire_lane)
            sub.trace_id = trace_id
            res = super().resolve(sub)
            ticket.result = res
            return res
        mass = sketch_kernels.join_words(tails[:, 0], tails[:, 1])
        for i, s in enumerate(self.slices):
            admitted = int(mass[i])
            if admitted:
                with s._lock:
                    s._note_mass_locked(admitted, ticket.now_us)
        wire_packed = None
        if ticket.wire_lane:
            wire_packed, remaining, retry, reset_at = wire_pack(
                allowed, remaining, retry, reset_at)
        res = BatchResult(allowed=allowed, limit=ticket.limit,
                          remaining=remaining, retry_after=retry,
                          reset_at=reset_at, limits=ticket.limits,
                          wire_packed=wire_packed)
        ticket.result = res
        return res

    @property
    def result_fetches(self) -> int:
        return self._fetches + super().result_fetches

    @property
    def result_native_unpacks(self) -> int:
        return self._native_unpacks + super().result_native_unpacks

    @property
    def override_lookup_dispatches(self) -> int:
        return self._override_lookups + super().override_lookup_dispatches

    # ------------------------------------------------ pipelined public API

    def launch_hashed(self, h64: np.ndarray,
                      ns: Optional[np.ndarray] = None, *,
                      now: Optional[float] = None) -> DispatchTicket:
        self._check_open()
        h64 = np.asarray(h64, dtype=np.uint64)
        ns_arr = (np.ones(h64.shape[0], dtype=np.int64) if ns is None
                  else np.asarray(ns, dtype=np.int64))
        t = self.clock.now() if now is None else float(now)
        if self._use_host_router(h64.shape[0]):
            return self._launch_split(h64, ns_arr,
                                      self.owner_of_hash(h64), t,
                                      premix=False, wire=False)
        return self._launch_routed_guarded(h64, ns_arr, t,
                                           premix=False, wire=False)

    def launch_ids(self, ids: np.ndarray,
                   ns: Optional[np.ndarray] = None, *,
                   now: Optional[float] = None,
                   wire: bool = False) -> DispatchTicket:
        self._check_open()
        ids = np.asarray(ids, dtype=np.uint64)
        ns_arr = (np.ones(ids.shape[0], dtype=np.int64) if ns is None
                  else np.asarray(ns, dtype=np.int64))
        t = self.clock.now() if now is None else float(now)
        if self._use_host_router(ids.shape[0]):
            return self._launch_split(ids, ns_arr, self.owner_of_id(ids),
                                      t, premix=True, wire=wire)
        return self._launch_routed_guarded(ids, ns_arr, t,
                                           premix=True, wire=wire)

    def launch_batch(self, keys: Sequence[str],
                     ns: Optional[Sequence[int]] = None, *,
                     now: Optional[float] = None) -> DispatchTicket:
        self._check_open()
        from ratelimiter_tpu.algorithms.base import check_key, check_n

        keys = list(keys)
        for k in keys:
            check_key(k)
        if ns is None:
            ns_arr = np.ones(len(keys), dtype=np.int64)
        else:
            from ratelimiter_tpu.core.errors import InvalidNError

            if len(ns) != len(keys):
                raise InvalidNError(
                    f"ns length {len(ns)} != keys length {len(keys)}")
            for n in ns:
                check_n(int(n))
            ns_arr = np.asarray(ns, dtype=np.int64)
        t = self.clock.now() if now is None else float(now)
        h64 = self._hash(keys)
        if self._use_host_router(h64.shape[0]):
            return self._launch_split(h64, ns_arr,
                                      self.owner_of_hash(h64), t,
                                      premix=False, wire=False)
        return self._launch_routed_guarded(h64, ns_arr, t,
                                           premix=False, wire=False)

    def _allow_batch(self, keys: list, ns: np.ndarray,
                     now: float) -> BatchResult:
        h64 = self._hash(keys)
        if self._use_host_router(h64.shape[0]):
            return super()._allow_batch(keys, ns, now)
        return self.resolve(self._launch_routed_guarded(
            h64, np.asarray(ns, dtype=np.int64), now,
            premix=False, wire=False))

    # ------------------------------------------------------------ prewarm

    def prewarm_routed(self, max_batch: int) -> None:
        """Compile the collective step for every pad bucket the doors
        can produce (the serving _prewarm's loop only reaches the
        slices; the collective step is a distinct program per L)."""
        top = 2 * max_batch
        size = 8
        while True:
            size = min(size, top)
            h = np.arange(size, dtype=np.uint64) + (1 << 62)
            self.allow_hashed(h, now=0.0)
            self.allow_ids(h, now=0.0)
            if size >= top:
                break
            size *= 2

    # -------------------------------------------------------------- stats

    def router_stats(self) -> dict:
        """Collective-path bookkeeping for /v1/health and /metrics."""
        return {"mode": "collective", "dispatches": self.dispatches,
                "placements": self.placements,
                "fallbacks": self.fallbacks,
                "fallback_reasons": dict(self._fallbacks)}
