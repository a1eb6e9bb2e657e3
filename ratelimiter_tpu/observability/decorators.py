"""Observability decorators around RateLimiter.

The reference's L4 layer, designed but unbuilt
(``docs/ADR/003-decorator-pattern-for-observability.md:44-125``,
``docs/ARCHITECTURE.md:269-285``): wrappers that implement the same
RateLimiter surface, so they compose with each other and with any backend
— ``MetricsDecorator(LoggingDecorator(create_limiter(cfg, "sketch")))`` —
and pass the full contract suite (tests/test_decorators.py instantiates
it for a decorated limiter).

Metric names follow the reference's spec (``docs/ARCHITECTURE.md:550-566``):

* ``rate_limiter_requests_total{algorithm,result}`` — result is allowed /
  denied / fail_open / error:<kind>; counts *requests* (allow_n(n) is one).
* ``rate_limiter_decisions_allowed_total`` / ``_denied_total`` — device-side
  per-decision counters, one reduction over the batch mask (free on TPU).
* ``rate_limiter_latency_seconds{algorithm,op}`` — wall time of the inner
  call (the batched dispatch for allow_batch).
* ``rate_limiter_batch_size`` — histogram of decisions per inner dispatch.
* ``rate_limiter_storage_errors_total{algorithm}`` — backend failures,
  whether surfaced as fail-open or raised (analog of
  ``rate_limiter_redis_errors_total``).
"""

from __future__ import annotations

import logging
import threading
import time
from contextlib import contextmanager
from typing import Optional, Sequence

import numpy as np

from ratelimiter_tpu.algorithms.base import RateLimiter
from ratelimiter_tpu.core.errors import (
    ClosedError,
    InvalidKeyError,
    InvalidNError,
    StorageUnavailableError,
)
from ratelimiter_tpu.core.types import BatchResult, Result
from ratelimiter_tpu.observability import metrics as m
from ratelimiter_tpu.observability import tracing


class LimiterDecorator(RateLimiter):
    """Base decorator: delegates the whole RateLimiter surface to ``inner``.

    Validation, clocking, and locking all live in the inner limiter; the
    decorator only observes. Subclasses override the ``_observe_*`` hooks.
    """

    def __init__(self, inner: RateLimiter):
        # Deliberately NOT calling RateLimiter.__init__: config is already
        # validated by (and owned by) the inner limiter; re-validating here
        # would double any validation side effects.
        self.inner = inner
        self._closed = False

    # Delegated attributes ------------------------------------------------

    @property
    def config(self):  # type: ignore[override]
        return self.inner.config

    @property
    def clock(self):  # type: ignore[override]
        return self.inner.clock

    # Public surface (decorated) ------------------------------------------

    def allow(self, key: str, *, now: Optional[float] = None) -> Result:
        return self.allow_n(key, 1, now=now)

    def allow_n(self, key: str, n: int, *, now: Optional[float] = None) -> Result:
        t0 = time.perf_counter()
        try:
            res = self.inner.allow_n(key, n, now=now)
        except Exception as exc:
            self._observe_error("allow_n", exc, time.perf_counter() - t0)
            raise
        self._observe_result("allow_n", res, n, time.perf_counter() - t0)
        return res

    def allow_batch(self, keys: Sequence[str], ns=None, *,
                    now: Optional[float] = None) -> BatchResult:
        t0 = time.perf_counter()
        try:
            out = self.inner.allow_batch(keys, ns, now=now)
        except Exception as exc:
            self._observe_error("allow_batch", exc, time.perf_counter() - t0)
            raise
        self._observe_batch("allow_batch", out, ns, time.perf_counter() - t0)
        return out

    def reset(self, key: str) -> None:
        t0 = time.perf_counter()
        try:
            self.inner.reset(key)
        except Exception as exc:
            self._observe_error("reset", exc, time.perf_counter() - t0)
            raise
        self._observe_op("reset", time.perf_counter() - t0)

    # Pipelined dispatch (ADR-010): launch passes through unobserved (it
    # only enqueues); the batch is observed ONCE, at resolve, where the
    # decisions actually exist. Explicit delegation is required — the
    # base class defines launch_batch/resolve, so __getattr__ would never
    # fire and the decorator would run the base eager fallback instead of
    # the backend's real pipelined path.

    @property
    def pipelined(self):  # type: ignore[override]
        return getattr(self.inner, "pipelined", False)

    def launch_batch(self, keys: Sequence[str], ns=None, *,
                     now: Optional[float] = None):
        return self.inner.launch_batch(keys, ns, now=now)

    def resolve(self, ticket):
        t0 = time.perf_counter()
        try:
            out = self.inner.resolve(ticket)
        except Exception as exc:
            self._observe_error("resolve", exc, time.perf_counter() - t0)
            raise
        self._observe_batch("resolve", out, None, time.perf_counter() - t0)
        return out

    # Hashed / raw-id lane (ADR-011): explicit delegation for the same
    # reason as launch_batch/resolve — subclasses (the breaker) must be
    # able to interpose, and the synchronous forms must be observed.
    # The serving doors detect lane SUPPORT on the undecorated backend
    # (hasattr on the decorator would now always be true), so these
    # definitions never advertise a lane the inner limiter lacks.

    def allow_hashed(self, h64, ns=None, *, now: Optional[float] = None):
        t0 = time.perf_counter()
        try:
            out = self.inner.allow_hashed(h64, ns, now=now)
        except Exception as exc:
            self._observe_error("allow_hashed", exc,
                                time.perf_counter() - t0)
            raise
        self._observe_batch("allow_hashed", out, ns,
                            time.perf_counter() - t0)
        return out

    def allow_ids(self, ids, ns=None, *, now: Optional[float] = None):
        t0 = time.perf_counter()
        try:
            out = self.inner.allow_ids(ids, ns, now=now)
        except Exception as exc:
            self._observe_error("allow_ids", exc, time.perf_counter() - t0)
            raise
        self._observe_batch("allow_ids", out, ns, time.perf_counter() - t0)
        return out

    def launch_hashed(self, h64, ns=None, *, now: Optional[float] = None):
        return self.inner.launch_hashed(h64, ns, now=now)

    def launch_ids(self, ids, ns=None, *, now: Optional[float] = None,
                   wire: bool = False):
        return self.inner.launch_ids(ids, ns, now=now, wire=wire)

    def close(self) -> None:
        self._closed = True
        self.inner.close()

    def update_limit(self, new_limit: int) -> None:
        # Delegate wholesale (config lives on the inner limiter; the
        # decorator's config property reflects it automatically).
        self.inner.update_limit(new_limit)

    def update_window(self, new_window: float) -> None:
        # Same: the base implementation would run against the decorator
        # and try to assign its read-only config property.
        self.inner.update_window(new_window)

    def capture_state(self):
        # Explicit (base defines it, so __getattr__ never fires): the
        # durability subsystem snapshots the BACKEND's state.
        return self.inner.capture_state()

    def save(self, path: str) -> None:
        self.inner.save(path)

    # Policy overrides: delegate wholesale rather than running the base
    # implementation against a delegated ``_policy_table`` — backends
    # that OVERRIDE the policy surface instead of owning a table (the
    # sliced mesh limiter fans every mutation out to its device slices,
    # ADR-012) must keep their semantics under any decorator stack.

    def set_override(self, key: str, limit: Optional[int] = None, *,
                     window_scale: float = 1.0):
        return self.inner.set_override(key, limit,
                                       window_scale=window_scale)

    def get_override(self, key: str):
        return self.inner.get_override(key)

    def delete_override(self, key: str) -> bool:
        return self.inner.delete_override(key)

    def list_overrides(self):
        return self.inner.list_overrides()

    def override_count(self) -> int:
        return self.inner.override_count()

    def sub_limiters(self):
        # The dispatch units live on the backend (a composite returns
        # its slices); the base impl would wrongly answer [decorator].
        return self.inner.sub_limiters()

    # Hierarchy surface (ADR-020): same explicit-delegation rule as the
    # policy surface — the base class defines these, so __getattr__
    # never fires, and the sliced mesh OVERRIDES them with write-all
    # semantics that must survive any decorator stack.

    def set_tenant(self, name: str, limit: Optional[int] = None, *,
                   weight: int = 1, floor: Optional[int] = None):
        return self.inner.set_tenant(name, limit, weight=weight,
                                     floor=floor)

    def delete_tenant(self, name: str) -> bool:
        return self.inner.delete_tenant(name)

    def assign_tenant(self, key: str, tenant: str) -> None:
        return self.inner.assign_tenant(key, tenant)

    def unassign_tenant(self, key: str) -> bool:
        return self.inner.unassign_tenant(key)

    def tenant_of(self, key: str) -> str:
        return self.inner.tenant_of(key)

    def get_tenant(self, name: str):
        return self.inner.get_tenant(name)

    def list_tenants(self):
        return self.inner.list_tenants()

    def set_global_limit(self, limit) -> None:
        return self.inner.set_global_limit(limit)

    def set_effective(self, scope: str, limit: int) -> int:
        return self.inner.set_effective(scope, limit)

    def effective_limits(self):
        return self.inner.effective_limits()

    def hierarchy_payload(self) -> dict:
        return self.inner.hierarchy_payload()

    def apply_hierarchy_payload(self, payload: dict) -> bool:
        return self.inner.apply_hierarchy_payload(payload)

    def hierarchy_stats(self) -> dict:
        return self.inner.hierarchy_stats()

    # Pass-through for backend extras (allow_hashed, inject_failure, ...) --

    def __getattr__(self, name: str):
        return getattr(self.inner, name)

    # Hooks ----------------------------------------------------------------

    def _observe_result(self, op: str, res: Result, n: int, dt: float) -> None:
        pass

    def _observe_batch(self, op: str, out: BatchResult, ns, dt: float) -> None:
        pass

    def _observe_op(self, op: str, dt: float) -> None:
        pass

    def _observe_error(self, op: str, exc: Exception, dt: float) -> None:
        pass

    # The abstract hooks are never reached (public surface is overridden),
    # but the ABC requires concrete definitions.

    def _allow_n(self, key: str, n: int, now: float) -> Result:  # pragma: no cover
        raise AssertionError("decorator delegates the public surface")

    def _reset(self, key: str) -> None:  # pragma: no cover
        raise AssertionError("decorator delegates the public surface")


def undecorated(limiter: RateLimiter) -> RateLimiter:
    """Peel the decorator stack down to the backend limiter (the object
    owning ``_state``/``_lock``, which checkpoint and DCN code needs)."""
    while isinstance(limiter, LimiterDecorator):
        limiter = limiter.inner
    return limiter


def _error_kind(exc: Exception) -> str:
    if isinstance(exc, StorageUnavailableError):
        return "storage_unavailable"
    if isinstance(exc, InvalidNError):
        return "invalid_n"
    if isinstance(exc, InvalidKeyError):
        return "invalid_key"
    if isinstance(exc, ClosedError):
        return "closed"
    return "internal"


class MetricsDecorator(LimiterDecorator):
    """Records the reference-specced metric families into a Registry
    (``docs/ADR/003:44-66``; names ``docs/ARCHITECTURE.md:550-566``)."""

    def __init__(self, inner: RateLimiter, registry: Optional[m.Registry] = None,
                 shard: str = "0"):
        super().__init__(inner)
        reg = registry if registry is not None else m.DEFAULT
        self.registry = reg
        #: Envelope-gauge label: with dispatch shards each shard's
        #: decorator must write its OWN series — a shared unlabeled gauge
        #: would be overwritten by whichever shard observed last, masking
        #: an overloaded shard behind a healthy one.
        self._shard = str(shard)
        self._algo = str(inner.config.algorithm)
        self._requests = reg.counter(
            "rate_limiter_requests_total",
            "Rate limit checks by algorithm and result")
        self._allowed = reg.counter(
            "rate_limiter_decisions_allowed_total",
            "Individual decisions allowed (device-side mask sum)")
        self._denied = reg.counter(
            "rate_limiter_decisions_denied_total",
            "Individual decisions denied (device-side mask sum)")
        self._latency = reg.histogram(
            "rate_limiter_latency_seconds",
            "Inner limiter call latency", m.LATENCY_BUCKETS)
        self._batch = reg.histogram(
            "rate_limiter_batch_size",
            "Decisions per batched dispatch", m.BATCH_BUCKETS)
        self._errors = reg.counter(
            "rate_limiter_storage_errors_total",
            "Backend failures (fail-open allowances included)")
        # Accuracy-envelope surface (windowed sketch only): exported so a
        # mis-sized geometry shows up on /metrics, not just in a log line
        # (SURVEY.md §7.4 hard part 3; docs/OPERATIONS.md §3).
        base = undecorated(inner)
        self._sketch = base if hasattr(base, "_period_mass") else None
        if self._sketch is not None:
            self._overload_g = reg.gauge(
                "rate_limiter_sketch_overload_periods",
                "Sub-windows whose admitted mass exceeded the geometry's "
                "accuracy budget (growing value = undersized sketch)")
            self._mass_g = reg.gauge(
                "rate_limiter_sketch_in_window_admitted_mass",
                "Admitted requests currently inside the sliding window")
            self._budget_g = reg.gauge(
                "rate_limiter_sketch_mass_budget",
                "Admitted-mass level where collision error reaches ~1% "
                "false denies for this geometry")
            self._budget_g.set(float(base.mass_budget), shard=self._shard)
        # Debt-slab surface (token-bucket sketch only): the continuous-
        # decay mirror of the mass watchdog (ROADMAP item 5 — strict
        # gating doesn't transfer, visibility does). Reading it costs a
        # device fetch under the backend lock, so the gauges refresh via
        # a scrape-time collect hook, never per decision. A sliced mesh
        # expands to its per-device slices, one series each.
        # Top-K consumer surface (heavy-hitter side table, ADR-016 §5):
        # promoted hot keys' exact in-window counts exported as ranked
        # gauges — refreshed by the same scrape-time collect-hook seam
        # as the debt slab (a K-slot device fetch per unit per scrape,
        # never the decide path). Consumer identity goes to /healthz
        # and /debug/audit as hash tokens; the gauge keys by RANK so
        # label cardinality stays bounded.
        self._hh_units = [
            (i, sl) for i, sl in enumerate(base.sub_limiters())
            if getattr(sl, "has_hh", False)]
        if self._hh_units:
            self._hh_top_g = reg.gauge(
                "rate_limiter_top_consumer_mass",
                "In-window admitted mass of the rank-N hottest tracked "
                "consumer (heavy-hitter side table; identities on "
                "/debug/audit)")
            self._hh_occ_g = reg.gauge(
                "rate_limiter_hh_tracked_consumers",
                "Occupied heavy-hitter slots (promoted hot keys "
                "currently tracked exactly)")
            reg.add_collect_hook(self._collect_consumers)
        self._debt_slabs = [
            (i, sl) for i, sl in enumerate(base.sub_limiters())
            if hasattr(sl, "debt_slab_stats")]
        if self._debt_slabs:
            self._debt_occ_g = reg.gauge(
                "rate_limiter_debt_slab_occupancy",
                "Max per-row fraction of debt cells with positive "
                "effective debt (colliding active keys share refill; "
                "hot rows throttle hot keys toward combined throughput)")
            self._debt_coll_g = reg.gauge(
                "rate_limiter_debt_slab_collision_probability",
                "Chance a fresh key reads an overestimated debt (an "
                "occupied cell in every sketch row) — errors are toward "
                "denying")
            reg.add_collect_hook(self._collect_debt_slab)
        # Collective router (ADR-024): how many frames it launched as one
        # mesh-wide program and how many it handed to the host router, by
        # reason, from router_stats() — exported at scrape like the native
        # door's stage sums, flight recorder on or off, so a run can tell
        # the collective path from its escape hatch.
        self._router = base if hasattr(base, "router_stats") else None
        if self._router is not None:
            self._coll_disp_g = reg.gauge(
                "rate_limiter_collective_dispatches_total",
                "Frames the collective mesh router launched as one "
                "shard_map program (cumulative; a frame that then "
                "overflowed a bin is counted here and under fallbacks)")
            self._coll_place_g = reg.gauge(
                "rate_limiter_collective_placements_total",
                "Operand shards the collective mesh router's launches "
                "sent to the devices (cumulative): one per array operand "
                "per addressable shard — one staged frame a launch, a "
                "row a device")
            self._coll_fall_g = reg.gauge(
                "rate_limiter_collective_fallbacks_total",
                "Frames the collective mesh router handed to the host "
                "router (cumulative): reason=overflow, a (source, "
                "destination) bin was full and the step left state "
                "untouched; reason=strict, the strict overload gate "
                "decides per slice before dispatch")
            reg.add_collect_hook(self._collect_router)

        # The sketch backends' own always-on dispatch counts, exported at
        # scrape over rate_limiter_door_dispatches_total. Device->host
        # fetches (the resolve half of a dispatch): device buffers resolve
        # has asked the device for — 1 a dispatch since the step packs
        # its result. Native unpacks: resolves whose reply columns the
        # one native pass built (1 a dispatch; 0 on a host that serves
        # from the NumPy twin). Override lookups (the device step's one
        # data-dependent branch): dispatches launched while the override
        # table held an entry, whose step ran the per-row binary search;
        # 0 on a deployment with no override.
        self._fetcher = base if hasattr(base, "result_fetches") else None
        if self._fetcher is not None:
            self._fetches_g = reg.gauge(
                "rate_limiter_result_fetches_total",
                "Device buffers resolve has fetched from the device "
                "(cumulative): one per array leaf per addressable shard "
                "of a dispatch's result")
            self._native_unpacks_g = reg.gauge(
                "rate_limiter_result_native_unpacks_total",
                "Resolves whose reply columns the native pass built in "
                "one call (cumulative); the rest were rebuilt by the "
                "NumPy twin, on a host that cannot build the extension")
            self._override_g = reg.gauge(
                "rate_limiter_override_lookup_dispatches_total",
                "Dispatches launched while the per-key override table "
                "held an entry (cumulative): their device step ran the "
                "override lookup, the others skipped it")
            reg.add_collect_hook(self._collect_dispatch_counts)

        # The windowed sketch's table accesses: rows decided by the steps
        # that access the table once a run of equal keys, and the runs
        # they accessed (the steps' own tail words, summed at resolve).
        self._accesses = (base if hasattr(base, "table_access_stats")
                          else None)
        if self._accesses is not None:
            self._access_rows_g = reg.gauge(
                "rate_limiter_sketch_rows_total",
                "Padded decision rows of the windowed sketch steps that "
                "access the table once a run of equal keys (cumulative); "
                "0 where no dispatch is large enough to carry the "
                "mechanism")
            self._access_runs_g = reg.gauge(
                "rate_limiter_sketch_access_runs_total",
                "Runs of equal keys those steps read and wrote the table "
                "for (cumulative): d gathers and d scatters a run where "
                "the per-row step makes them a row")
            reg.add_collect_hook(self._collect_table_accesses)

        # The dense backend's device-resident key directory (ADR-027): its
        # always-on counts, the steps' own tail words summed at resolve.
        self._directory = base if hasattr(base, "directory_stats") else None
        if self._directory is not None:
            self._dir_gauges = {
                name: reg.gauge(f"rate_limiter_directory_{name}", text)
                for name, text in (
                    ("lookups_total",
                     "Decision rows the dense backend's key directory "
                     "resolved to an entry or left unplaced (cumulative)"),
                    ("probes_total",
                     "Directory buckets examined for those rows "
                     "(cumulative): one a row when its key sits in its "
                     "home bucket"),
                    ("inserts_total",
                     "Keys the directory inserted (cumulative): a key's "
                     "first decision, or its first after a reclaim"),
                    ("unplaced_total",
                     "Decision rows whose key found no entry within the "
                     "probe bound and were answered by the fail-open/"
                     "closed policy (cumulative); 0 on a directory with "
                     "room"),
                    ("reclaimed_total",
                     "Entries the reclaim pass gave up, idle for two "
                     "windows (cumulative)"),
                    ("reclaim_passes_total",
                     "Reclaim passes run (cumulative): one when a launch "
                     "finds the directory over its gate (four fifths "
                     "full at 128 lanes and a probe bound of 8), at most "
                     "one in a sixteenth of a window — at every launch "
                     "once two hundredths further, the line —, and "
                     "after prune / reset"),
                    ("reclaim_seconds_total",
                     "Seconds launches were held by reclaim passes "
                     "(cumulative): the pass enqueued behind the steps "
                     "in flight and waited for, under the lane's lock"),
                    ("entries",
                     "Keys the directory holds now"),
                    ("capacity",
                     "Entries the directory has (--dense-capacity)"))}
            reg.add_collect_hook(self._collect_directory)

        # What the device holds: the limiter's resident state (the state
        # leaves' bytes per device, reckoned from their shapes when the
        # state was built) and, where the platform keeps memory statistics
        # (the CPU keeps none: no sample, not a 0), the bytes in use and
        # their high-water mark on each device that holds state. All set
        # at scrape, with no limiter lock taken and no buffer read. The
        # exact backend keeps its state on the host and exports none.
        self._resident = (base if hasattr(base, "state_resident_bytes")
                          else None)
        if self._resident is not None:
            self._resident_g = reg.gauge(
                "rate_limiter_state_resident_bytes",
                "Bytes of limiter state resident on the device: the "
                "nbytes of the state leaves the decision step is handed "
                "(sketch slabs, debt slab, the dense columns and the "
                "directory's keys)")
            self._device_stat_gauges = (
                ("peak_bytes_in_use", reg.gauge(
                    "rate_limiter_device_peak_bytes",
                    "Peak bytes in use on a device that holds limiter "
                    "state since the process started "
                    "(device.memory_stats(); no sample where the platform "
                    "reports none)")),
                ("bytes_in_use", reg.gauge(
                    "rate_limiter_device_bytes_in_use",
                    "Bytes in use now on a device that holds limiter "
                    "state (device.memory_stats(); no sample where the "
                    "platform reports none)")))
            reg.add_collect_hook(self._collect_device_memory)

    def _collect_device_memory(self) -> None:
        for dev, nbytes in self._resident.state_resident_bytes().items():
            device = str(dev.id)
            self._resident_g.set(float(nbytes), shard=self._shard,
                                 device=device)
            # The process's figures, not a shard's: no shard label, so
            # several dispatch shards on one device set one sample.
            stats = dev.memory_stats() or {}
            for key, gauge in self._device_stat_gauges:
                if key in stats:
                    gauge.set(float(stats[key]), device=device)

    def _collect_directory(self) -> None:
        st = self._directory.directory_stats()
        for name, gauge in self._dir_gauges.items():
            gauge.set(float(st[name.removesuffix("_total")]),
                      shard=self._shard)

    def _collect_table_accesses(self) -> None:
        st = self._accesses.table_access_stats
        self._access_rows_g.set(float(st["rows"]), shard=self._shard)
        self._access_runs_g.set(float(st["runs"]), shard=self._shard)

    def _collect_dispatch_counts(self) -> None:
        self._fetches_g.set(float(self._fetcher.result_fetches),
                            shard=self._shard)
        self._native_unpacks_g.set(
            float(self._fetcher.result_native_unpacks), shard=self._shard)
        self._override_g.set(
            float(self._fetcher.override_lookup_dispatches),
            shard=self._shard)

    def _collect_router(self) -> None:
        st = self._router.router_stats()
        self._coll_disp_g.set(float(st["dispatches"]), shard=self._shard)
        self._coll_place_g.set(float(st["placements"]), shard=self._shard)
        for reason, count in st["fallback_reasons"].items():
            self._coll_fall_g.set(float(count), shard=self._shard,
                                  reason=reason)

    def _collect_debt_slab(self) -> None:
        for i, sl in self._debt_slabs:
            st = sl.debt_slab_stats()
            self._debt_occ_g.set(st["occupancy"],
                                 shard=self._shard, slice=str(i))
            self._debt_coll_g.set(st["collision_p"],
                                  shard=self._shard, slice=str(i))

    def _collect_consumers(self) -> None:
        for i, sl in self._hh_units:
            st = sl.consumer_stats(k=5)
            self._hh_occ_g.set(float(st["occupied"]),
                               shard=self._shard, slice=str(i))
            top = st["top"]
            # Every rank 1..5 is written each scrape: when the list
            # SHRINKS (a hot key's window rolled off), the vacated
            # ranks must drop to 0 — a gauge only overwrites label
            # sets it is told to, so skipping them would leave phantom
            # heavy hitters frozen at their last mass forever.
            for rank in range(1, 6):
                mass = (float(top[rank - 1]["in_window"])
                        if rank <= len(top) else 0.0)
                self._hh_top_g.set(mass, shard=self._shard,
                                   slice=str(i), rank=str(rank))

    def close(self) -> None:
        # Unhook BEFORE closing: on the process-default registry a
        # leftover collect hook would pin this decorator (and the closed
        # backend's device arrays) forever and poke it on every scrape.
        if self._debt_slabs:
            self.registry.remove_collect_hook(self._collect_debt_slab)
        if self._hh_units:
            self.registry.remove_collect_hook(self._collect_consumers)
        if self._router is not None:
            self.registry.remove_collect_hook(self._collect_router)
        if self._fetcher is not None:
            self.registry.remove_collect_hook(self._collect_dispatch_counts)
        if self._accesses is not None:
            self.registry.remove_collect_hook(self._collect_table_accesses)
        if self._directory is not None:
            self.registry.remove_collect_hook(self._collect_directory)
        if self._resident is not None:
            self.registry.remove_collect_hook(self._collect_device_memory)
        super().close()

    def _observe_envelope(self) -> None:
        if self._sketch is not None:
            self._overload_g.set(float(self._sketch.overload_periods),
                                 shard=self._shard)
            self._mass_g.set(float(self._sketch.in_window_admitted_mass()),
                             shard=self._shard)
            self._budget_g.set(float(self._sketch.mass_budget),
                               shard=self._shard)

    def _result_label(self, res: Result) -> str:
        if res.fail_open:
            return "fail_open"
        return "allowed" if res.allowed else "denied"

    def _observe_result(self, op: str, res: Result, n: int, dt: float) -> None:
        self._requests.inc(algorithm=self._algo, result=self._result_label(res))
        if res.fail_open:
            self._errors.inc(algorithm=self._algo)
        if res.allowed:
            self._allowed.inc(algorithm=self._algo)
        else:
            self._denied.inc(algorithm=self._algo)
        self._latency.observe(dt, algorithm=self._algo, op=op)
        self._batch.observe(1.0)
        self._observe_envelope()

    def _observe_batch(self, op: str, out: BatchResult, ns, dt: float) -> None:
        b = len(out)
        n_allowed = int(np.sum(out.allowed))
        result = "fail_open" if out.fail_open else "mixed"
        self._requests.inc(b, algorithm=self._algo, result=result)
        if out.fail_open:
            self._errors.inc(algorithm=self._algo)
        self._allowed.inc(n_allowed, algorithm=self._algo)
        self._denied.inc(b - n_allowed, algorithm=self._algo)
        self._latency.observe(dt, algorithm=self._algo, op=op)
        self._batch.observe(float(b))
        self._observe_envelope()

    def _observe_op(self, op: str, dt: float) -> None:
        self._latency.observe(dt, algorithm=self._algo, op=op)

    def _observe_error(self, op: str, exc: Exception, dt: float) -> None:
        kind = _error_kind(exc)
        self._requests.inc(algorithm=self._algo, result=f"error:{kind}")
        if kind == "storage_unavailable":
            self._errors.inc(algorithm=self._algo)
        self._latency.observe(dt, algorithm=self._algo, op=op)


class TracingDecorator(LimiterDecorator):
    """Profiler-trace wrapper (the reference's planned OpenTelemetry
    ``TracingDecorator``, ``docs/ADR/003:115-124``, realized with the
    JAX profiler — the native tracing stack on TPU).

    Every decorated call runs inside a ``ratelimiter/<algo>/<op>``
    TraceMe, so device dispatches show up attributed by op/algorithm in
    xplane traces, and the stage spans below it (``tracing.span``: prep,
    place, step, finish) nest in it. Having one in the stack IS
    ``--trace``: construction turns the profiler sink of ``tracing.span``
    on for the process. ``capture(path)`` context-manages a full profiler
    capture around a workload for offline analysis (tensorboard /
    xprof)."""

    def __init__(self, inner: RateLimiter):
        super().__init__(inner)
        self._algo = str(inner.config.algorithm)
        tracing.annotate()

    def _annotation(self, op: str):
        return tracing.annotation(f"{self._algo}/{op}")

    def allow_n(self, key: str, n: int, *, now: Optional[float] = None) -> Result:
        with self._annotation("allow_n"):
            return self.inner.allow_n(key, n, now=now)

    def allow_batch(self, keys: Sequence[str], ns=None, *,
                    now: Optional[float] = None) -> BatchResult:
        with self._annotation("allow_batch"):
            return self.inner.allow_batch(keys, ns, now=now)

    def allow_hashed(self, h64, ns=None, *,
                     now: Optional[float] = None) -> BatchResult:
        with self._annotation("allow_hashed"):
            return self.inner.allow_hashed(h64, ns, now=now)

    def allow_ids(self, ids, ns=None, *,
                  now: Optional[float] = None) -> BatchResult:
        with self._annotation("allow_ids"):
            return self.inner.allow_ids(ids, ns, now=now)

    def reset(self, key: str) -> None:
        with self._annotation("reset"):
            self.inner.reset(key)

    # The pipelined hot path's two phases each get their own annotation
    # — without these, the default serving path's device work would show
    # up unattributed in xplane traces. The native door launches through
    # the hashed lanes, not launch_batch.

    def launch_batch(self, keys: Sequence[str], ns=None, *,
                     now: Optional[float] = None):
        with self._annotation("launch"):
            return self.inner.launch_batch(keys, ns, now=now)

    def launch_hashed(self, h64, ns=None, *, now: Optional[float] = None):
        with self._annotation("launch"):
            return self.inner.launch_hashed(h64, ns, now=now)

    def launch_ids(self, ids, ns=None, *, now: Optional[float] = None,
                   wire: bool = False):
        with self._annotation("launch"):
            return self.inner.launch_ids(ids, ns, now=now, wire=wire)

    def resolve(self, ticket):
        with self._annotation("resolve"):
            return self.inner.resolve(ticket)

    @contextmanager
    def capture(self, path: str):
        """Profile everything inside the with-block to ``path`` (xplane
        format; view with tensorboard's profile plugin)."""
        with tracing.profile(path):
            yield self


class CircuitBreakerDecorator(LimiterDecorator):
    """Circuit breaker around a limiter backend — the reference's planned
    resilience layer (``docs/ADR/002:170-197``, ``ROADMAP.md:104-108``:
    closed / open / half-open states), realized as a decorator.

    * closed: calls pass through; ``failure_threshold`` CONSECUTIVE
      backend failures (StorageUnavailableError raised, or a fail-open
      allowance — both mean the backend is down) trip the breaker;
    * open: for ``cooldown`` seconds the backend is not touched at all —
      decisions short-circuit per the limiter's fail-open/fail-closed
      policy (the point: a dead backend stops eating a dispatch timeout
      per request);
    * half-open: after the cooldown, exactly one probe call reaches the
      backend; success closes the breaker, failure re-opens it with a
      fresh cooldown.

    Time comes from the wrapped limiter's clock, so breaker tests use
    virtual time like everything else.
    """

    def __init__(self, inner: RateLimiter, *, failure_threshold: int = 5,
                 cooldown: float = 10.0,
                 registry: Optional[m.Registry] = None):
        super().__init__(inner)
        if failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        self.failure_threshold = failure_threshold
        self.cooldown = float(cooldown)
        self._state = "closed"
        self._consecutive = 0
        self._open_until = 0.0
        self._probe_inflight = False
        self._cb_lock = threading.Lock()
        #: Per-sub-limiter scoping (ADR-015 satellite): around a
        #: composite backend (the sliced mesh — sub_limiters() > 1), a
        #: failure ATTRIBUTED to one slice (exception ``slice_index`` /
        #: result ``fail_open_slices``) counts against that slice's own
        #: breaker state and NEVER the whole-keyspace one — one bad
        #: device must not short-circuit every other range. Unattributed
        #: failures (the whole backend down) trip the global breaker as
        #: before.
        self._scoped = len(undecorated(inner).sub_limiters()) > 1
        self._sub_consecutive: dict = {}
        self._sub_last_failure: dict = {}
        self._sub_open_until: dict = {}
        reg = registry if registry is not None else m.DEFAULT
        self._transitions = reg.counter(
            "rate_limiter_breaker_transitions_total",
            "Circuit breaker state transitions")
        self._short_circuits = reg.counter(
            "rate_limiter_breaker_short_circuits_total",
            "Decisions answered without touching the backend")

    @property
    def state(self) -> str:
        return self._state

    def sub_state(self, index: int, now: Optional[float] = None) -> str:
        """Scoped breaker state of one sub-limiter: "open" while its
        cooldown runs, else "closed" (slice-scoped failures never have
        a half-open phase here — the quarantine manager owns per-slice
        probing; this state is attribution bookkeeping)."""
        t = self.inner.clock.now() if now is None else float(now)
        with self._cb_lock:
            return ("open"
                    if self._sub_open_until.get(index, 0.0) > t
                    else "closed")

    def sub_states(self) -> dict:
        with self._cb_lock:
            return dict(self._sub_open_until)

    @staticmethod
    def _exc_slices(exc: Exception):
        si = getattr(exc, "slice_index", None)
        return [si] if si is not None else None

    def _trip(self, now: float) -> None:
        self._state = "open"
        self._open_until = now + self.cooldown
        self._transitions.inc(to="open")

    def _clear_probe(self) -> None:
        """Release the half-open probe slot without judging backend health.

        Non-storage exceptions (key/N validation, a closed limiter, bugs)
        say nothing about whether the backend recovered; counting them as
        failures would re-open the breaker on caller mistakes, and not
        clearing the slot would wedge the breaker permanently (every later
        call short-circuits because the probe "never returned").
        Only the call that OWNS the slot may release it.
        """
        with self._cb_lock:
            self._probe_inflight = False

    def _note_result(self, failed: bool, now: float, probe: bool,
                     slices=None) -> None:
        with self._cb_lock:
            if probe:
                self._probe_inflight = False
            if failed:
                if slices and self._scoped:
                    # Slice-attributed failure: count against the named
                    # slices only. The whole-keyspace breaker must keep
                    # admitting traffic for every other range — that is
                    # the regression a single-slice fault storm used to
                    # cause (it tripped the global breaker). "Consecutive"
                    # is cooldown-windowed: a failure more than one
                    # cooldown after the slice's previous one restarts
                    # its count (a healthy frame can't clear it — frames
                    # not touching the slice say nothing about it — so
                    # isolated transients must not accumulate forever).
                    for s in slices:
                        last = self._sub_last_failure.get(s, 0.0)
                        stale = now - last > self.cooldown
                        self._sub_last_failure[s] = now
                        c = (1 if stale
                             else self._sub_consecutive.get(s, 0) + 1)
                        self._sub_consecutive[s] = c
                        if (c >= self.failure_threshold
                                and self._sub_open_until.get(s, 0.0)
                                <= now):
                            self._sub_open_until[s] = now + self.cooldown
                            self._transitions.inc(to="open",
                                                  slice=str(s))
                    return
                self._consecutive += 1
                if (self._state == "half-open"
                        or self._consecutive >= self.failure_threshold):
                    self._trip(now)
            else:
                self._consecutive = 0
                if self._state != "closed":
                    self._state = "closed"
                    self._transitions.inc(to="closed")

    def _admit_call(self, now: float) -> Optional[bool]:
        """None = short-circuit; False = admitted (breaker closed);
        True = admitted as THE half-open probe (this call owns the slot
        and is the only one allowed to release it — a concurrent
        closed-state call that later fails must not free a slot it never
        held, or two probes could run at once)."""
        with self._cb_lock:
            if self._state == "closed":
                return False
            if self._state == "open" and now >= self._open_until:
                self._state = "half-open"
                self._transitions.inc(to="half-open")
            if self._state == "half-open" and not self._probe_inflight:
                self._probe_inflight = True
                return True
            return None

    def _short_circuit(self, b: int, now: float):
        self._short_circuits.inc(b)
        cfg = self.inner.config
        reset_at = now + float(cfg.window)
        if not cfg.fail_open:
            raise StorageUnavailableError(
                f"circuit breaker open (cooldown {self.cooldown:g}s)")
        if b == 1:
            from ratelimiter_tpu.core.types import fail_open_result

            return fail_open_result(cfg.limit, reset_at)
        from ratelimiter_tpu.core.types import batch_fail_open

        return batch_fail_open(b, cfg.limit, reset_at)

    def allow_n(self, key: str, n: int, *, now: Optional[float] = None) -> Result:
        t = self.inner.clock.now() if now is None else float(now)
        probe = self._admit_call(t)
        if probe is None:
            return self._short_circuit(1, t)
        try:
            res = self.inner.allow_n(key, n, now=now)
        except StorageUnavailableError as exc:
            self._note_result(True, t, probe, self._exc_slices(exc))
            raise
        except BaseException:
            if probe:
                self._clear_probe()
            raise
        self._note_result(res.fail_open, t, probe,
                          getattr(res, "fail_open_slices", None))
        return res

    def allow_batch(self, keys: Sequence[str], ns=None, *,
                    now: Optional[float] = None) -> BatchResult:
        t = self.inner.clock.now() if now is None else float(now)
        probe = self._admit_call(t)
        if probe is None:
            return self._short_circuit(len(keys), t)
        try:
            out = self.inner.allow_batch(keys, ns, now=now)
        except StorageUnavailableError as exc:
            self._note_result(True, t, probe, self._exc_slices(exc))
            raise
        except BaseException:
            if probe:
                self._clear_probe()
            raise
        self._note_result(out.fail_open, t, probe,
                          getattr(out, "fail_open_slices", None))
        return out

    # Pipelined path (ADR-010): the breaker admits (or short-circuits) at
    # LAUNCH — an open breaker must not enqueue device work at all — and
    # judges backend health at RESOLVE, where failure actually surfaces.
    # Probe ownership rides the ticket's meta field between the phases.

    def launch_batch(self, keys: Sequence[str], ns=None, *,
                     now: Optional[float] = None):
        t = self.inner.clock.now() if now is None else float(now)
        probe = self._admit_call(t)
        if probe is None:
            from ratelimiter_tpu.core.types import DispatchTicket

            return DispatchTicket(result=self._short_circuit(len(keys), t))
        try:
            ticket = self.inner.launch_batch(keys, ns, now=now)
        except StorageUnavailableError as exc:
            self._note_result(True, t, probe, self._exc_slices(exc))
            raise
        except BaseException:
            if probe:
                self._clear_probe()
            raise
        ticket.meta = ("breaker", t, probe)
        return ticket

    # Hashed / raw-id lane (ADR-011): the breaker guards every dispatch
    # entry point identically — an open breaker must not enqueue device
    # work for hashed frames any more than for string batches.

    def _guarded_sync(self, fn, b: int, now):
        t = self.inner.clock.now() if now is None else float(now)
        probe = self._admit_call(t)
        if probe is None:
            return self._short_circuit(b, t)
        try:
            out = fn()
        except StorageUnavailableError as exc:
            self._note_result(True, t, probe, self._exc_slices(exc))
            raise
        except BaseException:
            if probe:
                self._clear_probe()
            raise
        self._note_result(out.fail_open, t, probe,
                          getattr(out, "fail_open_slices", None))
        return out

    def _guarded_launch(self, fn, b: int, now):
        t = self.inner.clock.now() if now is None else float(now)
        probe = self._admit_call(t)
        if probe is None:
            from ratelimiter_tpu.core.types import DispatchTicket

            return DispatchTicket(result=self._short_circuit(b, t))
        try:
            ticket = fn()
        except StorageUnavailableError as exc:
            self._note_result(True, t, probe, self._exc_slices(exc))
            raise
        except BaseException:
            if probe:
                self._clear_probe()
            raise
        ticket.meta = ("breaker", t, probe)
        return ticket

    def allow_hashed(self, h64, ns=None, *, now=None):
        return self._guarded_sync(
            lambda: self.inner.allow_hashed(h64, ns, now=now),
            len(h64), now)

    def allow_ids(self, ids, ns=None, *, now=None):
        return self._guarded_sync(
            lambda: self.inner.allow_ids(ids, ns, now=now), len(ids), now)

    def launch_hashed(self, h64, ns=None, *, now=None):
        return self._guarded_launch(
            lambda: self.inner.launch_hashed(h64, ns, now=now),
            len(h64), now)

    def launch_ids(self, ids, ns=None, *, now=None, wire: bool = False):
        return self._guarded_launch(
            lambda: self.inner.launch_ids(ids, ns, now=now, wire=wire),
            len(ids), now)

    def resolve(self, ticket):
        tag = None
        if (isinstance(ticket.meta, tuple) and ticket.meta
                and ticket.meta[0] == "breaker"):
            tag = ticket.meta
            ticket.meta = None
        try:
            out = self.inner.resolve(ticket)
        except StorageUnavailableError as exc:
            if tag is not None:
                self._note_result(True, tag[1], tag[2],
                                  self._exc_slices(exc))
            raise
        except BaseException:
            if tag is not None and tag[2]:
                self._clear_probe()
            raise
        if tag is not None:
            self._note_result(out.fail_open, tag[1], tag[2],
                              getattr(out, "fail_open_slices", None))
        return out


class LoggingDecorator(LimiterDecorator):
    """Structured logging wrapper (``docs/ADR/003:68-91``): decisions at
    DEBUG, fail-open allowances at WARNING, errors at ERROR.

    Keys on the scalar path are logged at the caller's discretion:
    by default as given (the caller owns PII policy, as in the
    reference), or — with ``redact_keys=True`` — as the splitmix64 hash
    of the key's finalized u64 hash (``key#<16 hex>``), an irreversible
    but stable token that still correlates log lines per key without
    writing raw identifiers (user ids, API tokens, emails) into log
    storage. The PII trust boundary is documented in
    docs/OPERATIONS.md §6.

    Fail-open WARNINGs carry ``fail_open_slices`` when the result
    attributes the degradation (a quarantined mesh range, ADR-015), so
    a degraded-range line is actionable — it names WHICH slice's key
    range is answering fabricated allowances, not just that some frame
    somewhere failed open.
    """

    def __init__(self, inner: RateLimiter,
                 logger: Optional[logging.Logger] = None, *,
                 redact_keys: bool = False):
        super().__init__(inner)
        self.logger = logger if logger is not None else logging.getLogger(
            "ratelimiter_tpu")
        self._algo = str(inner.config.algorithm)
        self.redact_keys = bool(redact_keys)

    def _fmt_key(self, key: str) -> str:
        if not self.redact_keys:
            return key
        from ratelimiter_tpu.ops.hashing import key_token

        # Shared token rule (ops/hashing.key_token): redacted log lines
        # stay joinable with journal key_hash fields.
        return key_token(key)

    @staticmethod
    def _fo_slices(res) -> str:
        attr = getattr(res, "fail_open_slices", None)
        return f" fail_open_slices={sorted(attr)}" if attr else ""

    # Scalar path: overridden (not just hooked) so the KEY is in scope
    # for the log line — the base hooks deliberately do not carry it.

    def allow_n(self, key: str, n: int, *,
                now: Optional[float] = None) -> Result:
        t0 = time.perf_counter()
        try:
            res = self.inner.allow_n(key, n, now=now)
        except Exception as exc:
            self._observe_error("allow_n", exc, time.perf_counter() - t0)
            raise
        dt = time.perf_counter() - t0
        if res.fail_open:
            self.logger.warning(
                "fail-open allowance algorithm=%s key=%s n=%d "
                "latency=%.6f%s",
                self._algo, self._fmt_key(key), n, dt, self._fo_slices(res))
        elif self.logger.isEnabledFor(logging.DEBUG):
            self.logger.debug(
                "decision algorithm=%s key=%s allowed=%s n=%d remaining=%d "
                "latency=%.6f",
                self._algo, self._fmt_key(key), res.allowed, n,
                res.remaining, dt)
        return res

    def reset(self, key: str) -> None:
        # Quota-erase is audit-worthy: always logged, same redaction.
        t0 = time.perf_counter()
        try:
            self.inner.reset(key)
        except Exception as exc:
            self._observe_error("reset", exc, time.perf_counter() - t0)
            raise
        self.logger.info("reset algorithm=%s key=%s latency=%.6f",
                         self._algo, self._fmt_key(key),
                         time.perf_counter() - t0)

    def _observe_batch(self, op: str, out: BatchResult, ns, dt: float) -> None:
        if out.fail_open:
            self.logger.warning(
                "fail-open batch algorithm=%s size=%d latency=%.6f%s",
                self._algo, len(out), dt, self._fo_slices(out))
        elif self.logger.isEnabledFor(logging.DEBUG):
            self.logger.debug(
                "batch algorithm=%s size=%d allowed=%d latency=%.6f",
                self._algo, len(out), int(np.sum(out.allowed)), dt)

    def _observe_error(self, op: str, exc: Exception, dt: float) -> None:
        self.logger.error("limiter error op=%s algorithm=%s error=%s",
                          op, self._algo, exc)
