"""Multi-chip limiters: the collective mesh tier and the sliced serving tier.

Two complementary multi-device deployments share this module:

* ``MeshSketchLimiter`` / ``MeshTokenBucketLimiter`` — the collective
  tier: state replicated on every chip of a ``jax.sharding.Mesh``, the
  request batch sharded positionally, coherence via the all_gather/psum
  merge modes in parallel/mesh_kernels.py. Any chip may see any key; a
  decision pays a collective, never a network RTT.

* ``SlicedMeshLimiter`` — the slice-parallel SERVING tier (ADR-012,
  ``--backend mesh``): one independent, device-pinned single-chip limiter
  per device, and every key routed to its owning slice by hash. The
  decide path is COLLECTIVE-FREE — no cross-chip traffic at all — so
  serving throughput scales with the slice, and each key's decisions are
  bit-identical to a single-device limiter (the oracle property the
  serving tier tests pin). The gather/delta merge modes above remain the
  background-reconciliation story for workloads that cannot route.

This is the capability analog of the reference's Redis Cluster scale-out
(``docs/ARCHITECTURE.md:199-219``): the sliced tier shards *state* by key
ownership exactly as Redis Cluster shards its keyspace — but the routing
hop happens in the serving front door (C++ shard router / host hash), not
as a per-decision network RTT.
"""

from __future__ import annotations

import logging
from typing import List, Optional, Sequence

import numpy as np

from ratelimiter_tpu.algorithms.base import RateLimiter
from ratelimiter_tpu.algorithms.hashed_lane import HashedLane, _pad_size
from ratelimiter_tpu.algorithms.sketch import (
    SketchLimiter,
    SketchTokenBucketLimiter,
)
from ratelimiter_tpu.core.clock import Clock
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.core.errors import CheckpointError
from ratelimiter_tpu.core.types import (
    Algorithm,
    BatchResult,
    DispatchTicket,
    wire_pack,
)
from ratelimiter_tpu.observability import tracing
from ratelimiter_tpu.parallel import mesh_kernels
from ratelimiter_tpu.parallel.mesh import make_mesh


def _warn_delta() -> None:
    # The only configuration in the codebase that relaxes the strict
    # never-over-admit invariant — say so once, loudly.
    logging.getLogger(__name__).warning(
        "merge='delta': cross-chip admission is eventually consistent; a "
        "key can be over-admitted up to n_chips*limit within one step "
        "(bounded staleness, see docs/ADR/002-mesh-merge-modes.md). Use "
        "merge='gather' for strict exactness.")


class _MeshPlacement:
    """What placing a sketch limiter on a mesh changes, and all of it:
    the batch is sharded over the mesh axis, state and scalar operands
    are replicated, and the serving step is the shard_map'd one
    (``_build_step``, the base class's one program hook). Reset and
    rollover are replicated computations on replicated state — the base
    class's single-chip controls, as they are.

    Args (of every mesh limiter):
        config: limiter configuration (validated as usual).
        mesh: a 1-D ``jax.sharding.Mesh``; default = all visible devices.
        merge: "gather" (bit-exact global sequencing via all_gather — the
            default, and the only mode that preserves the reference's
            strict never-over-admit contract) or "delta" (one psum per
            step, <=1 step staleness: a key hammered from every chip in the
            SAME step can be over-admitted up to n_chips * limit in that
            step; converged and denying from the next step on). See
            parallel/__init__ and docs/ADR/002 for the tradeoff.
        clock: time source (tests inject ManualClock).
    """

    def __init__(self, config: Config, clock: Optional[Clock] = None, *,
                 mesh=None, merge: str = "gather"):
        if merge == "delta":
            _warn_delta()
        # Before the base constructor: it installs the steps, and the
        # step hook below reads the mesh.
        self.mesh = mesh if mesh is not None else make_mesh()
        self.merge = merge
        self.n_chips = int(np.prod(self.mesh.devices.shape))
        super().__init__(config, clock)
        self._state = mesh_kernels.replicate_state(self._state, self.mesh)
        self._note_resident()

    def _build_step(self, cfg: Config, premix: bool):
        return mesh_kernels.build_mesh_hashed_step(
            cfg, self.mesh, self.merge, premix=premix)

    def _padded_size(self, b: int) -> int:
        per_chip = _pad_size(max(1, -(-b // self.n_chips)))
        return per_chip * self.n_chips

    #: The shard_map'd step packs its shard's rows and no tail.
    _tail_words = HashedLane._tail_words

    def _stage_operands(self, buf: np.ndarray, padded: int) -> tuple:
        # The slot's three views, placed explicitly in one call: ids and
        # n sharded by batch, the timestamp replicated (the mesh steps
        # narrow n and now_us on device, like the single-chip step).
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        batch = NamedSharding(self.mesh, P(mesh_kernels.AXIS))
        return tuple(jax.device_put(
            (buf[:padded], buf[padded:2 * padded], buf[2 * padded]),
            (batch, batch, NamedSharding(self.mesh, P()))))

    def _place_replicated(self, arr: np.ndarray):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(arr, NamedSharding(self.mesh, P()))

    def _fence_dispatch(self, outs) -> None:
        # At most ONE in-flight collective execution on the host platform:
        # xla:cpu's all_gather/psum rendezvous parks device-pool threads
        # until every participant arrives, so two concurrent n_chips-wide
        # executions can each hold a subset of the pool and starve the
        # other forever (observed under the 150-thread contract storm:
        # a dozen in-flight steps, every rank logging "waiting for all
        # participants to arrive", zero progress). Completing the step
        # while the dispatch lock is still held caps the stream at one
        # rendezvous, which a starved pool always drains. Real devices
        # serialize executions in the hardware queue — the fence there
        # would only re-order the wait, so it stays CPU-only.
        if self.mesh.devices.flat[0].platform == "cpu":
            import jax

            jax.block_until_ready((self._state, outs))


class MeshSketchLimiter(_MeshPlacement, SketchLimiter):
    """Sketch limiter whose dispatch spans every chip of a mesh
    (arguments: _MeshPlacement)."""

    def _apply_window(self, new_cfg: Config) -> None:
        """Dynamic window on a mesh: the ring is migrated by a plain
        (unsharded) kernel, so place it replicated again."""
        super()._apply_window(new_cfg)
        with self._lock:
            self._state = mesh_kernels.replicate_state(self._state, self.mesh)
            self._note_resident()


class MeshTokenBucketLimiter(_MeshPlacement, SketchTokenBucketLimiter):
    """Sketched token bucket spanning a mesh: replicated debt slab, batch
    sharded over chips, same merge modes and staleness contract as
    MeshSketchLimiter (the scalar decay is deterministic on replicated
    state, so only the debt increments need a collective)."""


# ===================================================================
#                      slice-parallel serving tier
# ===================================================================

#: What a slice may be: the backends whose single-chip limiter takes a
#: ``device=`` (``create_limiter``'s names for them).
SLICE_BACKENDS = ("sketch", "dense")


def build_slices(config: Config, clock: Optional[Clock] = None, *,
                 backend: str = "sketch",
                 n_devices: Optional[int] = None,
                 devices: Optional[Sequence] = None) -> List[HashedLane]:
    """One device-pinned single-chip limiter per device (the slices of
    ``SlicedMeshLimiter``; the native front door mounts them directly as
    its dispatch shards, ADR-012) — the ONE place a slice's class is
    chosen, from the backend and the algorithm as ``create_limiter``
    chooses a single limiter's: ``sketch`` gives token-bucket configs
    the sketched token bucket and everything else the windowed sketch,
    ``dense`` the exact table (a slice holds ``config.dense.capacity``
    entries, as a sketch slice is ``config.sketch.width`` wide)."""
    import jax

    from ratelimiter_tpu.core.errors import InvalidConfigError

    if backend not in SLICE_BACKENDS:
        raise InvalidConfigError(
            f"no device slices of backend {backend!r}; expected one of "
            f"{SLICE_BACKENDS}")
    if devices is None:
        devices = jax.devices()
    n = n_devices if n_devices is not None else config.mesh.devices
    if n is not None:
        if n < 1:
            raise InvalidConfigError(
                f"mesh needs at least 1 device, got {n}")
        if n > len(devices):
            raise InvalidConfigError(
                f"mesh wants {n} devices but only {len(devices)} are "
                f"visible (XLA_FLAGS=--xla_force_host_platform_device_"
                f"count=N on CPU)")
        devices = list(devices)[:n]
    if backend == "dense":
        from ratelimiter_tpu.algorithms.dense import DenseLimiter

        return [DenseLimiter(config, clock, device=d) for d in devices]
    cls = (SketchTokenBucketLimiter
           if config.algorithm is Algorithm.TOKEN_BUCKET else SketchLimiter)
    # Hierarchy scopes on a hash-partitioned mesh: each slice enforces an
    # equal share of every tenant/global limit (effective // n_slices —
    # the static-split rule; ADR-020), since slices share no counters.
    return [cls(config, clock, device=d, hier_divisor=len(list(devices)))
            for d in devices]


class MeshDispatchTicket(DispatchTicket):
    """Composite ticket for one frame split across slices.

    ``subs`` holds (slice_index, positions, slice_ticket) triples;
    resolve() scatters each slice's results back to the frame's original
    positions. A frame fully owned by one slice skips the split (its
    slice ticket passes through, preserving the packed wire
    buffers). ``DispatchTicket.meta`` stays free for the decorator stack
    (the circuit breaker parks judgment state there)."""

    __slots__ = ("subs",)

    def __init__(self, result=None):
        super().__init__(result)
        self.subs = None


class SlicedMeshLimiter(RateLimiter):
    """Slice-parallel serving limiter (``--backend mesh``, ADR-012).

    One independent single-chip limiter (``build_slices``: windowed
    sketch or sketched token bucket per ``config.algorithm``, or with
    ``backend="dense"`` the exact table — ``--backend dense
    --mesh-devices N``) is pinned to each of the mesh's devices; every
    key is routed to its OWNING slice by hash:

    * pre-hashed keys (``allow_hashed``/``launch_hashed``): owner =
      ``h64 % n_slices``;
    * raw u64 ids (``allow_ids``/``launch_ids``): owner =
      ``splitmix64(id) % n_slices`` — the same router the native door's
      T_ALLOW_HASHED parse applies, so both surfaces agree;
    * string keys: hashed exactly as the single-chip limiter hashes them
      (prefix + hash_strings_u64), then the ``h64`` rule.

    The decide path is collective-free: a frame is partitioned host-side
    (one ``argsort`` over the owner vector), each touched slice gets one
    independent pipelined dispatch on its own device, and results scatter
    back to frame order at resolve. Per-key decisions are therefore
    BIT-IDENTICAL to a single-device limiter fed that key's traffic —
    the oracle property tests/test_mesh_serving.py pins. Cross-slice
    consistency needs none: slices share no keys by construction.

    The collective MeshSketchLimiter (replicated state, gather/delta
    merges) remains the right tool when requests CANNOT be routed (any
    chip may see any key); see the module docstring and ADR-012 §4.
    """

    pipelined = True

    def __init__(self, config: Config, clock: Optional[Clock] = None, *,
                 backend: str = "sketch",
                 n_devices: Optional[int] = None,
                 devices: Optional[Sequence] = None):
        super().__init__(config, clock)
        self.slices = build_slices(self.config, self.clock, backend=backend,
                                   n_devices=n_devices, devices=devices)
        self.n_slices = len(self.slices)
        self._CKPT_KIND = f"mesh:{self.slices[0]._CKPT_KIND}"
        self._backend = backend
        if backend == "dense":
            # Exact slices: the composite answers for their directories
            # as one (what MetricsDecorator and the door's control lane
            # look for; a sketch mesh has none of the three, as a sketch
            # limiter has none).
            self.prune = self._prune
            self.key_count = self._key_count
            self.directory_stats = self._directory_stats
        #: Failure-domain isolation (ADR-015, opt-in via
        #: ``MeshSpec.quarantine``): every slice is wrapped in a
        #: SliceGuard enforcing a per-slice dispatch deadline and
        #: degraded answers for quarantined ranges; ``self.quarantine``
        #: is the shared state machine (None = subsystem off and the
        #: hot path byte-identical to the unguarded build).
        self.quarantine = None
        if self.config.mesh.quarantine:
            from ratelimiter_tpu.parallel.quarantine import (
                QuarantineManager,
                SliceGuard,
            )

            spec = self.config.mesh
            self.quarantine = QuarantineManager(
                self.n_slices, clock=self.clock,
                probe_interval=spec.probe_interval,
                failure_threshold=spec.failure_threshold)
            self.slices = [
                SliceGuard(s, i, self.quarantine,
                           deadline=spec.slice_deadline)
                for i, s in enumerate(self.slices)]

    # ------------------------------------------------------------ routing

    def _hash(self, keys: List[str]) -> np.ndarray:
        """Prefix + hash exactly as the slices do (slice 0 is the
        canonical implementation; all slices share one config)."""
        return self.slices[0]._hash(list(keys))

    def owner_of_hash(self, h64: np.ndarray) -> np.ndarray:
        """Owning slice index per finalized u64 hash."""
        return (np.asarray(h64, np.uint64)
                % np.uint64(self.n_slices)).astype(np.int64)

    def owner_of_id(self, ids: np.ndarray) -> np.ndarray:
        """Owning slice index per RAW u64 id (the hashed wire lane):
        finalize with splitmix64 first, exactly like the native door's
        per-id shard router (server.cpp T_ALLOW_HASHED parse)."""
        from ratelimiter_tpu.ops.hashing import splitmix64

        return self.owner_of_hash(splitmix64(np.asarray(ids, np.uint64)))

    def owner_of_key(self, key: str) -> int:
        return int(self.owner_of_hash(self._hash([key]))[0])

    # ----------------------------------------------------- split dispatch

    def _launch_split(self, arrays: np.ndarray, ns: np.ndarray,
                      owners: np.ndarray, now: float, *,
                      premix: bool, wire: bool) -> MeshDispatchTicket:
        """Partition one frame by owning slice and launch one pipelined
        dispatch per touched slice. ``arrays`` holds finalized hashes
        (premix=False) or raw ids (premix=True — the slice finalizes
        in-step). Single-owner frames pass through unsplit, preserving
        the slice ticket's packed wire buffers."""
        b = int(arrays.shape[0])

        def sub_launch(lim, a, n_arr):
            if premix:
                return lim.launch_ids(a, n_arr, now=now, wire=wire)
            return lim.launch_hashed(a, n_arr, now=now)

        first = int(owners[0]) if b else 0
        if b == 0 or self.n_slices == 1 or not np.any(owners != first):
            t = MeshDispatchTicket()
            t.subs = [(first, None, sub_launch(self.slices[first],
                                               arrays, ns))]
            t.b = b
            t.limit = self.config.limit
            # Launch-time decision timestamp (the audit tap mirrors the
            # frame with the now it was DECIDED at, not resolve time —
            # ADR-016).
            t.t_sec = now
            return t
        # One argsort partitions the whole frame; per-slice position
        # arrays come out contiguous (stable sort keeps frame order
        # within a slice, so same-key sequencing inside the frame is
        # preserved — a key's requests all land on its slice in order).
        rec = tracing.RECORDER
        t_r0 = tracing.now() if rec is not None else 0
        order = np.argsort(owners, kind="stable")
        sorted_owners = owners[order]
        bounds = np.searchsorted(sorted_owners, np.arange(self.n_slices + 1))
        t = MeshDispatchTicket()
        t.subs = []
        for s in range(self.n_slices):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if lo == hi:
                continue
            pos = order[lo:hi]
            t.subs.append((s, pos, sub_launch(self.slices[s],
                                              arrays[pos], ns[pos])))
        t.b = b
        t.limit = self.config.limit
        t.t_sec = now
        # Wire frames reassemble the packed buffers at resolve (the
        # scatter-back path) — only meaningful on the raw-id lane, the
        # one surface whose sub-launches pack on device.
        t.wire = bool(wire and premix)
        if rec is not None:
            # "route": the owner partition + per-slice sub-launches.
            # The frame's trace id is stamped on the ticket AFTER launch
            # returns (the door owns it), so this span carries 0 — it
            # still appears on the frame's thread between "launch" start
            # and the device spans.
            rec.record("route", t_r0, tracing.now(), batch=b)
        return t

    def resolve(self, ticket: DispatchTicket) -> BatchResult:
        """Resolve every slice dispatch and scatter results back to the
        frame's original positions — completion is ONE barrier per frame
        (a single ``block_until_ready`` over every sub-dispatch, ADR-013),
        not a per-slice wait chain, so the frame finishes when the
        SLOWEST slice does regardless of resolution order. Failure
        semantics across slices are non-transactional, the same contract
        as the native door's multi-shard frames: a fail-closed error on
        one slice fails the frame, but other slices' quota stands;
        fail-open slices answer fail-open and the frame's flag ORs over
        slices."""
        if ticket.result is not None:
            return ticket.result
        subs = getattr(ticket, "subs", None)
        if subs is None:
            from ratelimiter_tpu.core.errors import RateLimiterError

            raise RateLimiterError(  # pragma: no cover - misuse guard
                "foreign ticket reached SlicedMeshLimiter.resolve")
        if len(subs) == 1 and subs[0][1] is None:
            s, _, sub = subs[0]
            try:
                res = self.slices[s].resolve(sub)
            except Exception as exc:
                if getattr(exc, "slice_index", None) is None:
                    try:
                        exc.slice_index = s
                    except Exception:  # noqa: BLE001 — best-effort
                        pass
                raise
            ticket.result = res
            return res
        # Single completion barrier: wait for EVERY slice's device work
        # in one call, then the per-slice resolves below are pure
        # (already-hot) fetches + bookkeeping. Errors surface in the
        # per-slice resolve, which owns the fail-open/closed contract.
        rec = tracing.RECORDER
        trace = getattr(ticket, "trace_id", 0)
        outs = [sub.outs for _, _, sub in subs
                if getattr(sub, "outs", None) is not None]
        if self.quarantine is not None:
            # Quarantine mode (ADR-015): NO global barrier — a wedged
            # device would hang it indefinitely. Each slice's guard
            # bounds its own resolve with the per-slice deadline
            # instead; the frame finishes within one deadline budget of
            # its slowest (possibly dead) slice.
            outs = []
        if outs:
            t_b0 = tracing.now() if rec is not None else 0
            try:
                import jax

                jax.block_until_ready(outs)
            except Exception:
                pass  # the owning slice's resolve reports it properly
            if rec is not None:
                # The frame's ONE completion barrier (ADR-013): every
                # per-slice span below links to it through the shared
                # trace id — the parent→slice→device tree the span
                # oracle walks (ADR-014).
                rec.record("barrier", t_b0, tracing.now(), trace_id=trace,
                           batch=ticket.b)
        b = ticket.b
        allowed = np.zeros(b, dtype=bool)
        remaining = np.zeros(b, dtype=np.int64)
        retry = np.zeros(b, dtype=np.float64)
        reset_at = np.zeros(b, dtype=np.float64)
        limits = None
        fail_open = False
        #: Per-slice fail-open attribution (ADR-015 / satellite 1): when
        #: EVERY fail-open contribution names its slice, the frame's
        #: result carries the union so the breaker decorator can scope
        #: the failure instead of tripping the whole keyspace.
        fo_slices: list = []
        fo_unattributed = False
        err = None
        wire = bool(getattr(ticket, "wire", False))
        for s, pos, sub in subs:
            t_s0 = tracing.now() if rec is not None else 0
            try:
                res = self.slices[s].resolve(sub)
            except Exception as exc:  # fail-closed slice: finish the rest
                if getattr(exc, "slice_index", None) is None:
                    try:
                        exc.slice_index = s
                    except Exception:  # noqa: BLE001 — best-effort
                        pass
                if rec is not None:
                    rec.record("slice", t_s0, tracing.now(),
                               trace_id=trace, shard=s,
                               outcome=tracing.ERROR)
                err = err if err is not None else exc
                continue
            if rec is not None:
                rec.record("slice", t_s0, tracing.now(), trace_id=trace,
                           shard=s, batch=len(res),
                           outcome=tracing.FAIL_OPEN if res.fail_open
                           else tracing.OK)
            allowed[pos] = res.allowed
            remaining[pos] = res.remaining
            retry[pos] = res.retry_after
            reset_at[pos] = res.reset_at
            if res.fail_open:
                attr = getattr(res, "fail_open_slices", None)
                if attr:
                    fo_slices.extend(attr)
                else:
                    fo_unattributed = True
            fail_open = fail_open or res.fail_open
            wire = wire and res.wire_packed is not None
            if res.limits is not None:
                if limits is None:
                    limits = np.full(b, self.config.limit, dtype=np.int64)
                limits[pos] = res.limits
        if err is not None:
            raise err
        wire_packed = None
        if wire:
            # The frame-order columns packed once more (ADR-013), so the
            # wire encoder still frames from packed buffers (memoryview
            # column slices, no per-row host math); single-owner frames
            # pass the slice's own buffers through untouched above.
            wire_packed, remaining, retry, reset_at = wire_pack(
                allowed, remaining, retry, reset_at)
        res = BatchResult(allowed=allowed, limit=self.config.limit,
                          remaining=remaining, retry_after=retry,
                          reset_at=reset_at, fail_open=fail_open,
                          limits=limits, wire_packed=wire_packed)
        if fail_open and fo_slices and not fo_unattributed:
            res.fail_open_slices = sorted(set(fo_slices))
        ticket.result = res
        return res

    # ------------------------------------------------- pipelined public API

    def launch_hashed(self, h64: np.ndarray,
                      ns: Optional[np.ndarray] = None, *,
                      now: Optional[float] = None) -> MeshDispatchTicket:
        self._check_open()
        h64 = np.asarray(h64, dtype=np.uint64)
        ns_arr = (np.ones(h64.shape[0], dtype=np.int64) if ns is None
                  else np.asarray(ns, dtype=np.int64))
        t = self.clock.now() if now is None else float(now)
        return self._launch_split(h64, ns_arr, self.owner_of_hash(h64), t,
                                  premix=False, wire=False)

    def launch_ids(self, ids: np.ndarray,
                   ns: Optional[np.ndarray] = None, *,
                   now: Optional[float] = None,
                   wire: bool = False) -> MeshDispatchTicket:
        self._check_open()
        ids = np.asarray(ids, dtype=np.uint64)
        ns_arr = (np.ones(ids.shape[0], dtype=np.int64) if ns is None
                  else np.asarray(ns, dtype=np.int64))
        t = self.clock.now() if now is None else float(now)
        return self._launch_split(ids, ns_arr, self.owner_of_id(ids), t,
                                  premix=True, wire=wire)

    def launch_batch(self, keys: Sequence[str],
                     ns: Optional[Sequence[int]] = None, *,
                     now: Optional[float] = None) -> MeshDispatchTicket:
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
        return self._launch_split(h64, ns_arr, self.owner_of_hash(h64), t,
                                  premix=False, wire=False)

    def allow_hashed(self, h64: np.ndarray,
                     ns: Optional[np.ndarray] = None, *,
                     now: Optional[float] = None) -> BatchResult:
        return self.resolve(self.launch_hashed(h64, ns, now=now))

    def allow_ids(self, ids: np.ndarray,
                  ns: Optional[np.ndarray] = None, *,
                  now: Optional[float] = None) -> BatchResult:
        return self.resolve(self.launch_ids(ids, ns, now=now))

    def _allow_batch(self, keys: list, ns: np.ndarray,
                     now: float) -> BatchResult:
        h64 = self._hash(keys)
        return self.resolve(self._launch_split(
            h64, ns, self.owner_of_hash(h64), now,
            premix=False, wire=False))

    def _allow_n(self, key: str, n: int, now: float):
        return self.slices[self.owner_of_key(key)].allow_n(key, n, now=now)

    # --------------------------------------------------- control plane

    def _reset(self, key: str) -> None:
        self.slices[self.owner_of_key(key)].reset(key)

    def update_limit(self, new_limit: int) -> None:
        self._check_open()
        for s in self.slices:
            s.update_limit(new_limit)
        from dataclasses import replace

        self.config = replace(self.config, limit=new_limit)

    def update_window(self, new_window: float) -> None:
        self._check_open()
        for s in self.slices:
            s.update_window(new_window)
        from dataclasses import replace

        self.config = replace(self.config, window=float(new_window))

    # The exact slices' directory controls (attached by ``__init__`` for
    # ``backend="dense"``; a sketch slice holds no key and has none):
    # every slice, each pass under its own lane's lock.

    def _prune(self, now: Optional[float] = None) -> int:
        self._check_open()
        return sum(s.prune(now) for s in self.slices)

    def _key_count(self) -> int:
        return sum(s.key_count() for s in self.slices)

    def _directory_stats(self) -> dict:
        """The slices' ``DenseLimiter.directory_stats``, summed: the
        host's entries and capacity, every count cumulative."""
        stats = [s.directory_stats() for s in self.slices]
        return {k: sum(st[k] for st in stats) for k in stats[0]}

    # Policy overrides apply on EVERY slice (idempotent for non-owners —
    # their copy is simply never queried for the key), the same rule as
    # the native door's shard router; reads route to the owner.

    def set_override(self, key: str, limit: Optional[int] = None, *,
                     window_scale: float = 1.0):
        self._check_open()
        ov = None
        for s in self.slices:
            ov = s.set_override(key, limit, window_scale=window_scale)
        return ov

    def get_override(self, key: str):
        self._check_open()
        return self.slices[self.owner_of_key(key)].get_override(key)

    def delete_override(self, key: str) -> bool:
        self._check_open()
        existed = False
        for s in self.slices:
            existed = s.delete_override(key) or existed
        return existed

    def list_overrides(self):
        self._check_open()
        return self.slices[0].list_overrides()

    def override_count(self) -> int:
        return self.slices[0].override_count()

    # Hierarchy surface: HierarchyFanout's write-all / read-one /
    # sum-stats semantics over the slices (each enforces its equal
    # share of the scope limits — ADR-020). Built per call: restore()
    # may rebuild self.slices.

    def _hier(self):
        from ratelimiter_tpu.hierarchy.fanout import HierarchyFanout

        self._check_open()
        return HierarchyFanout(self.slices)

    def set_tenant(self, name, limit=None, *, weight=1, floor=None):
        return self._hier().set_tenant(name, limit, weight=weight,
                                       floor=floor)

    def delete_tenant(self, name: str) -> bool:
        return self._hier().delete_tenant(name)

    def assign_tenant(self, key: str, tenant: str) -> None:
        self._hier().assign_tenant(key, tenant)

    def unassign_tenant(self, key: str) -> bool:
        return self._hier().unassign_tenant(key)

    def tenant_of(self, key: str) -> str:
        return self._hier().tenant_of(key)

    def get_tenant(self, name: str):
        return self._hier().get_tenant(name)

    def list_tenants(self):
        return self._hier().list_tenants()

    def set_global_limit(self, limit) -> None:
        self._hier().set_global_limit(limit)

    def set_effective(self, scope: str, limit: int) -> int:
        return self._hier().set_effective(scope, limit)

    def effective_limits(self):
        return self._hier().effective_limits()

    def hierarchy_payload(self) -> dict:
        return self._hier().hierarchy_payload()

    def apply_hierarchy_payload(self, payload: dict) -> bool:
        return self._hier().apply_hierarchy_payload(payload)

    def hierarchy_stats(self) -> dict:
        """Per-scope stats summed across slices (each slice's counters
        cover its hash-owned keys; the sum is the whole deployment's
        in-window mass). Effective/ceiling values come from slice 0's
        table — mutations are write-all, so the tables agree."""
        return self._hier().hierarchy_stats()

    # ------------------------------------------------- checkpoint seam

    def _check_combined(self) -> None:
        """The combined snapshot is the sketch slices' (re-bucketing onto
        another slice count is sketch arithmetic, parallel/reshard.py):
        a mesh of exact slices claims no durability and says so."""
        if self._backend == "dense":
            raise CheckpointError(
                f"no combined snapshot of {self._CKPT_KIND} slices: an "
                f"exact slice's entries sit where its own directory put "
                f"them and cannot be re-bucketed; save and restore each "
                f"slice by itself (sub_limiters())")

    def capture_state(self):
        """One combined snapshot over every slice: each slice captures
        under its own lock (device→host only — the persistence tier
        serializes and writes off-lock, ADR-009). Slices share no keys,
        so per-key consistency holds; cross-key skew between slice
        captures sits inside the documented one-interval staleness
        envelope. The slice count rides in the extras and restore
        REFUSES a different count — slice counters are only meaningful
        under the routing that produced them."""
        self._check_open()
        self._check_combined()
        arrays = {}
        extras = []
        for i, s in enumerate(self.slices):
            _, a, e = s.capture_state()
            arrays.update({f"slice{i}:{k}": v for k, v in a.items()})
            extras.append(e)
        return self._CKPT_KIND, arrays, {
            "n_slices": self.n_slices,
            "slice_extras": extras,
            "saved_at": self.clock.now(),
        }

    def restore(self, path: str) -> None:
        """Restore a combined snapshot. A snapshot taken under a
        DIFFERENT slice count is re-bucketed onto this mesh's geometry
        (parallel/reshard.py, ADR-018): clean splits copy state
        verbatim per new slice, merges take the conservative union
        (elementwise max after period alignment) — per-key override
        tables re-route exactly, estimates only rise, so the resharded
        mesh never over-admits relative to the source. The same math is
        available offline as ``tools/rebucket.py`` for cold resizes."""
        from ratelimiter_tpu.checkpoint import load_state

        self._check_open()
        self._check_combined()
        arrays, meta = load_state(path, self._CKPT_KIND, self.config)
        saved = int(meta.get("n_slices", -1))
        if saved != self.n_slices:
            from ratelimiter_tpu.parallel import reshard

            logging.getLogger(__name__).warning(
                "%s: snapshot holds %d slice(s) but this mesh runs %d "
                "device(s) — re-bucketing key-routed state onto the new "
                "geometry (conservative union: overrides exact, "
                "estimates only rise; ADR-018)", path, saved,
                self.n_slices)
            arrays, meta = reshard.rebucket_combined(
                arrays, meta, self.n_slices, self.config)
        extras = meta.get("slice_extras") or [{}] * self.n_slices
        for i, s in enumerate(self.slices):
            prefix = f"slice{i}:"
            sub = {k[len(prefix):]: v for k, v in arrays.items()
                   if k.startswith(prefix)}
            s._restore_loaded(sub, extras[i], label=f"{path}[slice{i}]")

    def restore_slice(self, path: str, index: int) -> None:
        """Slice-scoped restore (ADR-015): replace ONE slice's state
        with its sub-dictionary of the combined snapshot at ``path``,
        leaving every other slice untouched. This is the recovery half
        of quarantine — a slice rejoining routing restores from the
        newest snapshot (plus the WAL suffix the persistence tier
        replays, recover.recover_unit) before it serves again. Same
        slice-count refusal as a full restore."""
        from ratelimiter_tpu.checkpoint import load_state

        self._check_open()
        self._check_combined()
        if not 0 <= index < self.n_slices:
            raise CheckpointError(
                f"restore_slice: slice {index} out of range "
                f"[0, {self.n_slices})")
        arrays, meta = load_state(path, self._CKPT_KIND, self.config)
        saved = int(meta.get("n_slices", -1))
        if saved != self.n_slices:
            raise CheckpointError(
                f"{path}: snapshot holds {saved} slice(s) but this mesh "
                f"runs {self.n_slices} — a SINGLE slice cannot be "
                f"re-bucketed in place (its peers' state would stay on "
                f"the old routing); use a full restore(), which "
                f"re-buckets the whole snapshot onto the new geometry "
                f"(parallel/reshard.py, ADR-018), or resize the snapshot "
                f"offline with tools/rebucket.py")
        extras = meta.get("slice_extras") or [{}] * self.n_slices
        prefix = f"slice{index}:"
        sub = {k[len(prefix):]: v for k, v in arrays.items()
               if k.startswith(prefix)}
        self.slices[index]._restore_loaded(
            sub, extras[index], label=f"{path}[slice{index}]")

    # ------------------------------------------------- fault injection

    def inject_failure(self, exc: Optional[Exception] = None) -> None:
        for s in self.slices:
            s.inject_failure(exc)

    def heal(self) -> None:
        for s in self.slices:
            s.heal()

    # ----------------------------------------------------- introspection

    def sub_limiters(self):
        """The per-device slices (the serving tier's per-unit seam:
        DCN pushers/merges, prewarm, and the health envelope iterate
        these)."""
        return list(self.slices)

    def state_resident_bytes(self) -> dict:
        """The slices' own resident state
        (HashedLane.state_resident_bytes), summed per device."""
        out: dict = {}
        for s in self.slices:
            for dev, nbytes in s.state_resident_bytes().items():
                out[dev] = out.get(dev, 0) + nbytes
        return out

    def memory_bytes(self) -> int:
        return sum(self.state_resident_bytes().values())

    @property
    def result_fetches(self) -> int:
        """Device buffers the slices' resolves have fetched
        (SketchLimiter.result_fetches), summed."""
        return sum(s.result_fetches for s in self.slices)

    @property
    def result_native_unpacks(self) -> int:
        """Resolves whose columns the native pass built
        (HashedLane.result_native_unpacks), summed over the slices."""
        return sum(s.result_native_unpacks for s in self.slices)

    @property
    def table_access_stats(self) -> dict:
        """The slices' own table-access counts
        (SketchLimiter.table_access_stats), summed."""
        stats = [s.table_access_stats for s in self.slices]
        return {k: sum(st[k] for st in stats) for k in ("rows", "runs")}

    @property
    def override_lookup_dispatches(self) -> int:
        """Slice dispatches launched while the override table held an
        entry (SketchLimiter.override_lookup_dispatches), summed."""
        return sum(s.override_lookup_dispatches for s in self.slices)

    def in_window_admitted_mass(self) -> int:
        return sum(s.in_window_admitted_mass() for s in self.slices)

    @property
    def mass_budget(self) -> int:
        return sum(s.mass_budget for s in self.slices)

    @property
    def overload_periods(self) -> int:
        return sum(s.overload_periods for s in self.slices)

    def _close(self) -> None:
        for s in self.slices:
            s.close()
