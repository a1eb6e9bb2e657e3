"""SketchLimiter: the TPU_SKETCH flagship backend.

Approximate sliding-window rate limiting over a count-min sketch with
sub-window decay (ops/sketch_kernels.py). Properties:

* memory is O(depth x width x ring), independent of key cardinality —
  1M or 8M keys cost the same HBM (vs the reference's ~200 B/user in Redis,
  ``docs/ARCHITECTURE.md:458-469``);
* CMS overestimation can only cause false *denies* (availability, not
  correctness, is at stake); the rate is measured against the exact oracle
  by ratelimiter_tpu.evaluation (BASELINE.json metric: <= 1% on Zipf-1M);
* the fast path takes pre-hashed uint64 keys (``allow_hashed``); string
  keys are hashed host-side (ops/hashing.py).

Reset subtracts the key's estimate rather than deleting state (a sketch has
no per-key cells to delete); see _sketch_reset for why this errs toward
allowing. Failure semantics are identical to the dense backend (fail-open /
fail-closed on dispatch failure, ADR-002 parity).
"""

from __future__ import annotations

import logging
import threading
from typing import List, Optional

import numpy as np

from ratelimiter_tpu.algorithms.base import RateLimiter
from ratelimiter_tpu.algorithms.hashed_lane import HashedLane
from ratelimiter_tpu.core.clock import Clock, MICROS, to_micros
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.core.types import BatchResult, DispatchTicket
from ratelimiter_tpu.ops.hashing import split_hash

log = logging.getLogger("ratelimiter_tpu")


class SketchLimiter(HashedLane, RateLimiter):
    def __init__(self, config: Config, clock: Optional[Clock] = None, *,
                 device=None, hier_divisor: int = 1):
        """``device`` pins this limiter's state (and every staged batch)
        to one specific ``jax.Device`` instead of the process default —
        the slice seam of the slice-parallel serving tier (ADR-012,
        parallel/limiter.py): computation follows the committed state
        buffers, so N pinned limiters dispatch to N devices concurrently
        with no collective and no cross-device traffic. None keeps the
        default-device behavior bit-for-bit."""
        super().__init__(config, clock)
        self._device = device
        from ratelimiter_tpu.ops import sketch_kernels

        self._install_steps(self.config)
        self._state = self._pin_state(sketch_kernels.init_state(self.config))
        self._note_resident()
        self._window_us = to_micros(self.config.window)
        self._sub_us = sketch_kernels.sketch_geometry(self.config)[1]
        self._seed = self.config.sketch.seed
        self._lock = threading.Lock()
        self._init_staging()
        # Host mirror of state["last_period"]; drives rollover dispatches
        # (sketch_kernels._rollover explains why this is host-side).
        self._host_period = sketch_kernels._NEVER
        self._injected_failure: Optional[Exception] = None
        # Accuracy-envelope watchdog: admitted in-window mass vs the
        # geometry's calibrated budget (SketchParams.mass_budget). Host
        # integers only — no device cost.
        self._ring_sw = sketch_kernels.sketch_geometry(self.config)[2]
        self._mass_budget = self.config.sketch.mass_budget(self.config.limit)
        self._strict = self.config.sketch.overload_policy == "strict"
        self._period_mass: dict = {}
        self._warned_period = -1
        self.overload_periods = 0
        self._init_policy()
        self._init_hierarchy(hier_divisor)

    # ------------------------------------------------------------- policy

    def _init_policy(self) -> None:
        """Per-key limit overrides, resolved in-kernel. The search key is
        the (h1, h2) packing the CMS columns ride on; window scaling is
        impossible on a shared ring geometry, so only limits override."""
        from ratelimiter_tpu.policy import PolicyTable

        self._policy_table = PolicyTable(
            self.config, key_fn=self._policy_key,
            validator=self._policy_validate, window_scaling=False)
        self._policy_dev = None
        self._policy_dev_version = -1

    def _policy_validate(self, limit: int, _window_us: int) -> None:
        if limit >= (1 << 24):
            from ratelimiter_tpu.core.errors import InvalidConfigError

            raise InvalidConfigError(
                f"sketch backends require override limits < 2**24 "
                f"(f32-exact admission), got {limit}")

    def _policy_key(self, key: str) -> int:
        from ratelimiter_tpu.ops.policy_kernels import pack_halves_host

        h64 = self._hash([key])
        h1, h2 = split_hash(h64, self._seed)
        return int(pack_halves_host(h1, h2)[0])

    def _policy_device(self):
        """Replicated device copy of the override table (key + limit
        columns). Lock must be held; rebuilt when the table version moved."""
        t = self._policy_table
        if self._policy_dev is None or self._policy_dev_version != t.version:
            host = t.host_arrays()
            self._policy_dev = {
                "key": self._place_replicated(host["key"]),
                "limit": self._place_replicated(host["limit"]),
            }
            self._policy_dev_version = t.version
        return self._policy_dev

    def _policy_limits(self, h64: np.ndarray):
        """Host-side per-request effective limits for result assembly
        (None when no override matches)."""
        if not len(self._policy_table):
            return None
        from ratelimiter_tpu.ops.policy_kernels import pack_halves_host

        h1, h2 = split_hash(np.asarray(h64, np.uint64), self._seed)
        return self._policy_table.limits_for(pack_halves_host(h1, h2))

    # ---------------------------------------------------------- hierarchy

    def _init_hierarchy(self, divisor: int = 1) -> None:
        """Tenant + global cascade scopes (ADR-020), resolved in-kernel
        like the policy table. ``divisor`` is the per-unit share a
        hash-partitioned slice enforces (sliced mesh: n_slices)."""
        self._hier_table = None
        self._hier_dev = None
        self._hier_dev_version = -1
        if self.config.hierarchy.enabled:
            from ratelimiter_tpu.hierarchy import TenantTable

            self._hier_table = TenantTable(
                self.config, key_fn=self._policy_key, divisor=divisor)

    def _hier_device(self):
        """Replicated device copy of the cascade tables (key→tenant map +
        limit/weight columns). Lock must be held; rebuilt when the table
        version moved. None when the hierarchy is disabled."""
        t = self._hier_table
        if t is None:
            return None
        if self._hier_dev is None or self._hier_dev_version != t.version:
            host = t.host_arrays()
            self._hier_dev = {k: self._place_replicated(v)
                              for k, v in host.items()}
            self._hier_dev_version = t.version
        return self._hier_dev

    def _hier_counts(self) -> np.ndarray:
        """(T+1,) in-window admitted counts per scope (global at index
        T). Lock held for one reference read only (jax arrays are
        immutable — the consumer_stats discipline)."""
        with self._lock:
            # tn_totals only refreshes inside a dispatch; with zero
            # traffic an idle limiter would keep reporting the LAST
            # window's mass to the controller (tighten forever, relax
            # never). Kick the same rollover sweep a decision would.
            self._sync_period(to_micros(self.clock.now()))
            ref = self._state["tn_totals"]
        return np.asarray(ref)

    def hierarchy_stats(self) -> dict:
        from ratelimiter_tpu.core.config import HIER_UNLIMITED
        from ratelimiter_tpu.hierarchy.tenants import GLOBAL

        t = self._hier_table
        if t is None:
            return super().hierarchy_stats()
        counts = self._hier_counts()
        tenants = {}
        for name in t.tenant_names():
            ten = t.get_tenant(name)
            tenants[name] = {
                "tid": ten.tid,
                "in_window": int(counts[ten.tid]),
                "effective": t.effective_of(name),
                "ceiling": ten.limit or HIER_UNLIMITED,
                "floor": ten.floor,
                "weight": ten.weight,
            }
        return {"tenants": tenants,
                "global": {"in_window": int(counts[t.capacity]),
                           "effective": t.effective_of(GLOBAL),
                           "ceiling": t.global_ceiling},
                "divisor": t.divisor,
                "assignments": len(t.assignments())}

    def _sync_period(self, now_us: int) -> None:
        """Dispatch the rollover kernel if now_us entered a new sub-window.
        Must be called with self._lock held. The period rides as a NumPy
        scalar — an operand of the one launch, where an eager
        ``jnp.int64(p)`` is a transfer AND a program of its own."""
        p = now_us // self._sub_us
        if p > self._host_period:
            self._state = self._rollover(self._state, np.int64(p))
            self._host_period = p

    def _pin_state(self, state):
        """Commit freshly-built state to the pinned device (no-op without
        one): every later step follows these buffers, so a pinned limiter
        never touches another slice's device."""
        if self._device is None:
            return state
        import jax

        return {k: jax.device_put(v, self._device) for k, v in state.items()}

    # ------------------------------------------------ compiled programs

    @staticmethod
    def _kernels():
        """The module of this rule's step builders."""
        from ratelimiter_tpu.ops import sketch_kernels

        return sketch_kernels

    def _build_step(self, cfg: Config, premix: bool):
        """The compiled serving step for ``cfg`` — THE placement hook:
        a mesh placement overrides this one method. The step takes ONE
        staged uint64 buffer per batch and does the (h1, h2) split — and
        under ``premix`` (the raw-u64-id wire lane, launch_ids) the
        splitmix64 finalizer too — on the device (ADR-011), so the host
        never runs per-key hash math."""
        return self._kernels().build_hashed_step(cfg, premix=premix)

    def _install_steps(self, cfg: Config) -> None:
        """Swap in the programs compiled for ``cfg``. Called with
        self._lock held (or from __init__). The premix step is built
        lazily (_get_ids_step); reset (and the windowed rules' rollover)
        are the rule's single-chip controls on every placement — rare
        control-plane dispatches over (h1, h2) operands, replicated
        computations on a mesh's replicated state."""
        self._step = self._build_step(cfg, False)
        self._ids_step = None
        self._reset_step, *rollover = self._kernels().build_controls(cfg)
        self._rollover = rollover[0] if rollover else None

    def _get_ids_step(self):
        if self._ids_step is None:
            self._ids_step = self._build_step(self.config, True)
        return self._ids_step

    # The launch / resolve mechanism itself — the staging slot, the one
    # transfer, the ticket, the one fetch and their counts — is
    # algorithms/hashed_lane.HashedLane, shared with the dense backend;
    # below is what a sketch adds to it.

    def _gate_locked(self, b: int, now_us: int) -> Optional[BatchResult]:
        self._sync_period(now_us)
        if self._strict and self._over_budget_locked(now_us):
            # Strict overload policy: REJECT new admissions (no state
            # write, no dispatch) while admitted in-window mass exceeds
            # the geometry's accuracy budget — loud bounded denials
            # instead of silent unbounded misaccounting. Clears as
            # history ages out of the ring.
            return self._deny_all(b, now_us)
        return None

    def _step_args(self, slot: np.ndarray, padded: int) -> tuple:
        args = (self._state, *self._stage_operands(slot, padded),
                self._policy_device())
        if self._hier_table is not None:
            # Cascade tables ride as one extra replicated operand —
            # tenant ids derive on device, same dispatch.
            args = args + (self._hier_device(),)
        return args

    def _result_format(self) -> tuple:
        """``(rows, unpack)`` of this rule's packed result buffer."""
        from ratelimiter_tpu.ops import sketch_kernels

        return sketch_kernels.WINDOW_ROWS, sketch_kernels.unpack_window

    # The windowed step's table-access counts (table_access_stats): the
    # tail words of the programs that access the table once a run of
    # equal keys (ops/sortmerge._use_run_dedup), summed at resolve.
    _access_rows = 0
    _access_runs = 0

    def _tail_words(self, padded: int) -> int:
        from ratelimiter_tpu.ops import sketch_kernels

        return sketch_kernels.step_tail_words(padded,
                                              self.config.sketch.width)

    def _note_tail_locked(self, t: DispatchTicket, tails) -> None:
        rows, runs = (int(x) for x in tails[0])
        self._access_rows += rows
        self._access_runs += runs

    @property
    def table_access_stats(self) -> dict:
        """Cumulative, always on: ``rows`` the steps that access the
        table once a run of equal keys have decided (padded rows: what
        the per-row body would have accessed, d gathers and d scatters
        each) and the ``runs`` they accessed instead —
        ``rate_limiter_sketch_rows_total`` /
        ``rate_limiter_sketch_access_runs_total``. Both stay 0 where no
        dispatch is large enough to carry the mechanism."""
        with self._lock:
            return {"rows": self._access_rows, "runs": self._access_runs}

    def _over_budget_locked(self, now_us: int) -> bool:
        """Prune + check the admitted-mass ledger; counts/warns once per
        offending sub-window. Launched-but-unresolved tickets count at
        their full offered mass (pessimistic — their true admitted mass
        replaces the estimate at resolve), so the pipeline's in-flight
        window cannot slip admissions past the budget. Lock must be
        held."""
        p = now_us // self._sub_us
        if self._period_mass:
            p = max(p, max(self._period_mass))
        low = p - self._ring_sw
        for q in [q for q in self._period_mass if q <= low]:
            del self._period_mass[q]
        mass = sum(self._period_mass.values()) + self._inflight_mass
        if mass <= self._mass_budget:
            return False
        if p > self._warned_period:
            self._warned_period = p
            self.overload_periods += 1
            log.warning(
                "sketch overload (strict): admitted in-window mass %d "
                "exceeds the d=%d w=%d budget of %d — rejecting new "
                "admissions until history expires; size the geometry "
                "with SketchParams.for_load", mass,
                self.config.sketch.depth, self.config.sketch.width,
                self._mass_budget)
        return True

    def _deny_all(self, b: int, now_us: int) -> BatchResult:
        """Uniform denial batch for the strict overload path. Retry
        points at the next sub-window boundary: mass drains one
        sub-window at a time, so that is when admission could resume."""
        retry = ((now_us // self._sub_us + 1) * self._sub_us
                 - now_us) / MICROS
        cur_ws = (now_us // self._window_us) * self._window_us
        reset_at = (cur_ws + self._window_us) / MICROS
        return BatchResult(
            allowed=np.zeros(b, dtype=bool),
            limit=self.config.limit,
            remaining=np.zeros(b, dtype=np.int64),
            retry_after=np.full(b, retry, dtype=np.float64),
            reset_at=np.full(b, reset_at, dtype=np.float64),
        )

    # ------------------------------------------------- accuracy envelope

    def _note_mass_locked(self, admitted: int, now_us: int) -> None:
        """Track admitted in-window mass against the geometry's calibrated
        budget (SketchParams.mass_budget): collision error — and with it
        the false-deny rate — scales with this mass, so exceeding the
        budget means the geometry is undersized for the offered load.
        Warns loudly once per sub-window while overloaded. Lock must be
        held (callers pair this with the in-flight-mass bookkeeping in
        one acquisition — _retire_ticket)."""
        p = now_us // self._sub_us
        # Clamp forward like the kernels clamp now_us: after a backward
        # clock step the ledger would otherwise keep "future" periods
        # alive past pruning, inflating the in-window mass and firing
        # spurious undersized-geometry warnings.
        if self._period_mass:
            p = max(p, max(self._period_mass))
        self._period_mass[p] = self._period_mass.get(p, 0) + admitted
        low = p - self._ring_sw
        for q in [q for q in self._period_mass if q <= low]:
            del self._period_mass[q]
        mass = sum(self._period_mass.values())
        if mass > self._mass_budget and p > self._warned_period:
            self._warned_period = p
            self.overload_periods += 1
            log.warning(
                "sketch geometry undersized: admitted in-window mass "
                "%d exceeds the d=%d w=%d budget of %d at limit=%d — "
                "collision error is at the ~1%% false-deny level and "
                "grows with load; size the geometry with "
                "SketchParams.for_load(limit=%d, "
                "expected_window_mass=%d)",
                mass, self.config.sketch.depth, self.config.sketch.width,
                self._mass_budget, self.config.limit, self.config.limit,
                mass)

    def in_window_admitted_mass(self) -> int:
        """Admitted requests currently counted inside the sliding window
        (the quantity SketchParams.mass_budget bounds)."""
        with self._lock:
            return sum(self._period_mass.values())

    @property
    def mass_budget(self) -> int:
        return self._mass_budget

    # --------------------------------------------------------------- reset

    def _reset(self, key: str) -> None:
        import jax.numpy as jnp

        h64 = self._hash([key])
        h1, h2 = split_hash(h64, self._seed)
        now_us = to_micros(self.clock.now())
        with self._lock:
            self._sync_period(now_us)
            self._state = self._reset_step(
                self._state, self._place_replicated(h1),
                self._place_replicated(h2), jnp.int64(now_us))

    def _close(self) -> None:
        self._state = {}

    # ------------------------------------------------- dynamic config

    def _apply_config(self, new_cfg: Config) -> None:
        """Dynamic limit: geometry (window/sub-windows/depth/width) is
        unchanged, so the state arrays carry over; only the compiled
        steps (which bake the limit) are swapped."""
        with self._lock:
            self._install_steps(new_cfg)
            self._mass_budget = new_cfg.sketch.mass_budget(new_cfg.limit)

    def _apply_window(self, new_cfg: Config) -> None:
        """Dynamic window: migrate the ring onto the new sub-window
        geometry (ops/sketch_kernels._migrate_window — conservative
        re-bucketing, never over-admits), swap compiled steps, and
        re-bucket the mass-watchdog's period ledger by wall time."""
        from ratelimiter_tpu.ops import sketch_kernels

        migrate = sketch_kernels.build_migrate(self.config, new_cfg)
        new_sub = sketch_kernels.sketch_geometry(new_cfg)[1]
        new_sw = sketch_kernels.sketch_geometry(new_cfg)[2]
        import jax.numpy as jnp

        now_us = to_micros(self.clock.now())
        with self._lock:
            old_sub = self._sub_us
            self._state = migrate(self._state, jnp.int64(now_us))
            self._note_resident()    # the ring's length may have changed
            self._install_steps(new_cfg)
            self._window_us = to_micros(new_cfg.window)
            self._sub_us = new_sub
            self._ring_sw = new_sw
            self._host_period = now_us // new_sub
            self._period_mass = self._remap_mass(old_sub, new_sub)
            self._warned_period = -1
            # DCN bookkeeping is denominated in old-unit periods: drop it
            # (foreign subtraction against renumbered periods would be
            # wrong; the pusher detects the sub_us change and resets its
            # watermarks — parallel/dcn.py, serving/dcn_peer.py).
            if hasattr(self, "_dcn_foreign"):
                self._dcn_foreign = {}

    def _remap_mass(self, old_sub: int, new_sub: int) -> dict:
        merged: dict = {}
        for p, mass in self._period_mass.items():
            q = ((p + 1) * old_sub - 1) // new_sub
            merged[q] = merged.get(q, 0) + mass
        return merged

    # ------------------------------------------------- checkpoint/restore

    _CKPT_KIND = "sketch"
    #: State arrays that may be absent in older checkpoints and default
    #: to zeros on restore (see restore()). ``hh_owner2`` (added r5 for
    #: DCN export of promoted keys) restoring as zeros only means those
    #: owners' traffic stays local-only until re-promotion — decisions
    #: are unaffected (export_completed skips owner2==0 slots).
    _CKPT_OPTIONAL: tuple = ("hh_owner2",)

    def capture_state(self):
        """Lock-held device→host transfer of the full ring + policy
        columns (the np.asarray calls). This is the only part of a
        snapshot that blocks decisions — serialization and the fsynced
        write happen in the caller, off-lock
        (persistence/snapshotter.py). Format and staleness contract:
        ratelimiter_tpu/checkpoint.py."""
        self._check_open()
        with self._lock:
            arrays = {k: np.asarray(v) for k, v in self._state.items()}
            arrays.update(self._policy_table.snapshot_arrays())
            if self._hier_table is not None:
                arrays.update(self._hier_table.snapshot_arrays())
            extra = {"saved_at": self.clock.now()}
            hp = getattr(self, "_host_period", None)
            if hp is not None:
                extra["host_period"] = int(hp)
        return self._CKPT_KIND, arrays, extra

    def restore(self, path: str) -> None:
        """Replace device state with the snapshot at ``path``. Catch-up for
        elapsed time is automatic: the next dispatch's rollover sweep (or
        token-bucket decay) advances the restored state to 'now'."""
        from ratelimiter_tpu.checkpoint import load_state

        self._check_open()
        arrays, meta = load_state(path, self._CKPT_KIND, self.config)
        self._restore_loaded(arrays, meta, label=path)

    def _restore_loaded(self, arrays, meta, *,
                        label: str = "snapshot") -> None:
        """Apply already-loaded-and-validated snapshot arrays (the body
        of restore(); the sliced mesh limiter feeds each slice its own
        sub-dictionary of one combined snapshot — parallel/limiter.py).
        ``label`` names the source in error messages (the path, or
        path[sliceN] for a combined mesh snapshot)."""
        import jax

        with self._lock:
            # Overrides ride the snapshot (policy_* columns; absent in
            # older checkpoints -> empty table).
            self._policy_table.restore_arrays(arrays)
            self._policy_dev = None
            if self._hier_table is not None:
                # Cascade tables + controller-moved effective limits
                # (hier_* columns) — adaptive state resumes, it does not
                # snap back to the ceilings (ADR-020).
                self._hier_table.restore_arrays(arrays)
                self._hier_dev = None
            # Arrays added in later releases may default when absent from
            # an older checkpoint (each class lists the safe ones).
            for k in self._CKPT_OPTIONAL:
                if k not in arrays and k in self._state:
                    arrays[k] = np.zeros_like(np.asarray(self._state[k]))
            if set(arrays) != set(self._state):
                from ratelimiter_tpu.core.errors import CheckpointError

                raise CheckpointError(
                    f"{label}: state arrays {sorted(arrays)} != expected "
                    f"{sorted(self._state)}")
            # Preserve each buffer's placement (single-device or mesh-
            # replicated NamedSharding) — restore works identically for
            # SketchLimiter and MeshSketchLimiter.
            self._state = {
                k: jax.device_put(arrays[k], self._state[k].sharding)
                for k in self._state
            }
            if "host_period" in meta:
                self._host_period = int(meta["host_period"])

    # ----------------------------------------------------- introspection

    @property
    def has_hh(self) -> bool:
        """Whether the heavy-hitter side table is configured
        (SketchParams.hh_slots > 0)."""
        return "hh_owner" in self._state

    def consumer_stats(self, k: int = 10) -> dict:
        """Top-K consumer analytics off the heavy-hitter side table
        (ADR-016 §5): the hh slots already track promoted hot keys'
        EXACT in-window counts for admission — this read-only view
        exports them as analytics. Cost: the lock is held for three
        reference reads (jax arrays are immutable — same discipline as
        debt_slab_stats), then K-slot host fetches; scrape/healthz
        cadence only, never the decide path.

        Consumers are identified by their (h1, h2) hash pair rendered as
        one 64-bit hex token — irreversible (no raw keys leave the
        process, the PII boundary of OPERATIONS §6) yet stable across
        scrapes and slices, so dashboards can track a hot consumer over
        time. ``{"slots": 0}`` when the side table is off
        (SketchParams.hh_slots=0)."""
        if "hh_owner" not in self._state:
            return {"slots": 0, "occupied": 0, "top": []}
        with self._lock:
            owner_ref = self._state["hh_owner"]
            owner2_ref = self._state["hh_owner2"]
            totals_ref = self._state["hh_totals"]
        owner = np.asarray(owner_ref)
        owner2 = np.asarray(owner2_ref)
        totals = np.asarray(totals_ref)
        live = (owner != 0) & (totals > 0)
        idx = np.nonzero(live)[0]
        order = idx[np.argsort(totals[idx], kind="stable")[::-1]][:max(0, k)]
        total_mass = int(totals[live].sum())
        return {
            "slots": int(owner.shape[0]),
            "occupied": int((owner != 0).sum()),
            "tracked_mass": total_mass,
            "top": [{
                "consumer": f"{(int(owner[i]) << 32) | int(owner2[i]):016x}",
                "in_window": int(totals[i]),
                "share": round(int(totals[i]) / max(1, total_mass), 6),
            } for i in order],
        }


class SketchTokenBucketLimiter(SketchLimiter):
    """TOKEN_BUCKET at unbounded key cardinality: CMS over per-key *debt*
    (ops/bucket_kernels.py — the GCRA meter form of the reference's
    ``tokenbucket.go:23-52`` semantics). Continuous fractional refill,
    burst up to ``limit``, denial consumes nothing; overestimated debt can
    only cause false denies, never over-admission.

    Shares the SketchLimiter shell (hashing, padding, locking, fault
    injection, fail-open) and swaps the kernels: no sub-window ring, no
    rollover dispatches — decay is inside the step itself."""

    #: ``acc`` (the DCN export accumulator) was added after v0.1: older
    #: checkpoints restore with a zero accumulator (worst case: traffic
    #: from before the upgrade is never exported — local decisions and
    #: future exchange are unaffected).
    _CKPT_OPTIONAL = ("acc",)

    def __init__(self, config: Config, clock: Optional[Clock] = None, *,
                 device=None, hier_divisor: int = 1):
        RateLimiter.__init__(self, config, clock)
        self._device = device
        from ratelimiter_tpu.ops import bucket_kernels

        self._install_steps(self.config)
        self._state = self._pin_state(bucket_kernels.init_state(self.config))
        self._note_resident()
        self._window_us = to_micros(self.config.window)
        self._seed = self.config.sketch.seed
        self._lock = threading.Lock()
        self._init_staging()
        # The mass watchdog (and with it overload_policy="strict") is a
        # windowed-sketch concept; debt decays continuously
        # (_note_mass_locked).
        self._strict = False
        self._injected_failure: Optional[Exception] = None
        self._init_policy()
        self._init_hierarchy(hier_divisor)

    def _policy_validate(self, limit: int, _window_us: int) -> None:
        # Batch admission does exact int64 micro-token cumsums; the same
        # gate as the dense backend's micro-unit accounting.
        if limit * MICROS >= 2**42:
            from ratelimiter_tpu.core.errors import InvalidConfigError

            raise InvalidConfigError(
                f"override limit {limit} too large for micro-unit batch "
                "accounting (>= 2^42/1e6)")

    def _sync_period(self, now_us: int) -> None:
        """No ring, no rollover: decay happens inside every step."""

    def _result_format(self) -> tuple:
        from ratelimiter_tpu.ops import bucket_kernels

        return bucket_kernels.BUCKET_ROWS, bucket_kernels.unpack_bucket

    _tail_words = HashedLane._tail_words    # the bucket's step ships none

    def _hier_counts(self) -> np.ndarray:
        """Bucket-backend scope counters are fixed-window: counts from a
        previous window read as zero (the step zeroes them lazily)."""
        with self._lock:
            counts_ref = self._state["tn_counts"]
            period_ref = self._state["tn_period"]
        counts = np.asarray(counts_ref)
        cur_p = to_micros(self.clock.now()) // self._window_us
        if int(np.asarray(period_ref)) < cur_p:
            return np.zeros_like(counts)
        return counts

    @staticmethod
    def _kernels():
        from ratelimiter_tpu.ops import bucket_kernels

        return bucket_kernels

    def _note_mass_locked(self, admitted: int, now_us: int) -> None:
        """No mass watchdog for the debt sketch: debt decays continuously
        (no sub-window ring to bucket mass into) and overestimated debt
        self-corrects as it drains; the windowed calibration does not
        transfer. Geometry sizing guidance lives in docs/ALGORITHMS.md."""

    def in_window_admitted_mass(self) -> int:
        raise NotImplementedError(
            "the admitted-mass watchdog applies to windowed sketches "
            "only (debt decays continuously; see _note_mass_locked)")

    @property
    def mass_budget(self) -> int:
        raise NotImplementedError(
            "the admitted-mass watchdog applies to windowed sketches "
            "only (debt decays continuously; see _note_mass_locked)")

    def debt_slab_stats(self) -> dict:
        """Occupancy/collision visibility for the debt slab — the
        token-bucket mirror of the windowed mass watchdog (ROADMAP item
        5). Strict gating does not transfer here (_note_mass_locked:
        debt decays continuously and overestimates self-correct as they
        drain), but visibility does: rows running hot mean colliding
        active keys are sharing refill, throttling hot keys toward one
        key's worth of combined throughput — always toward denying; this
        surface says how likely that is right now.

        The lock is held for three REFERENCE reads only (jax arrays are
        immutable, so a consistent (debt, rem, last) triple taken under
        the lock reduces safely after release — the decide path never
        waits on this scrape's device work), and the liveness count is
        an on-device per-row reduction: /healthz and the /metrics
        scrape hooks fetch ``d`` scalars, never the (d, w) slab
        (0.5–24 MB at production widths). Per-row ``occupancy`` counts
        cells whose EFFECTIVE debt is positive (stored debt minus the
        global decay the next step would apply — stored cells go stale
        the moment traffic stops, so raw nonzero counts would read idle
        slabs as full). ``occupancy`` is the max over rows;
        ``collision_p`` is the product over rows — the chance a fresh
        key lands on an occupied cell in EVERY row, which is what it
        takes for the min-over-rows read to overestimate its debt."""
        import jax.numpy as jnp

        from ratelimiter_tpu.ops import bucket_kernels

        kw = bucket_kernels.step_statics(self.config)
        d, w = kw["d"], kw["w"]
        with self._lock:
            debt = self._state["debt"]
            rem_ref = self._state["rem"]
            last_ref = self._state["last"]
        now_us = to_micros(self.clock.now())
        # The SAME decay the next step would apply — _decay is the one
        # source of the elapsed/clamp arithmetic (scalar-safe jnp ops,
        # so the device refs feed it directly).
        decay, _ = bucket_kernels._decay(
            {"last": last_ref, "rem": rem_ref}, now_us,
            rate_num=kw["rate_num"], rate_den=kw["rate_den"])
        live_rows = np.asarray(jnp.sum(debt > decay, axis=1))
        occ_rows = live_rows / float(w)
        return {
            "depth": int(d),
            "width": int(w),
            "cells": int(d * w),
            "nonzero_cells": int(live_rows.sum()),
            "occupancy_rows": [round(float(o), 6) for o in occ_rows],
            "occupancy": round(float(occ_rows.max(initial=0.0)), 6),
            "collision_p": round(float(np.prod(occ_rows)), 9),
        }

    def _apply_config(self, new_cfg: Config) -> None:
        """Dynamic limit: refill rate (limit/window) and capacity both
        change; the debt slab carries over, CLAMPED to the new capacity —
        the exact mirror of the token-form backends clamping levels to
        [0, new_cap], so lowering a limit recovers identically across
        backends. The sub-micro-token decay remainder is denominated in
        the old rate fraction, so it resets (forfeits < 1 micro-token of
        accrued refill, toward denying)."""
        import jax.numpy as jnp

        cap = new_cfg.limit * MICROS
        with self._lock:
            self._install_steps(new_cfg)
            self._state = dict(
                self._state,
                debt=jnp.minimum(self._state["debt"], cap),
                rem=self._place_replicated(np.asarray(0, np.int64)))

    def _apply_window(self, new_cfg: Config) -> None:
        """Dynamic window for the debt sketch: the window only sets the
        refill rate (limit/window), so the kernels swap and accumulated
        debt stands (it now drains at the new rate — the same semantics
        as the token-form backends). The decay remainder is denominated
        in the old rate fraction, so it resets (forfeits < 1 micro-token
        toward denying)."""
        with self._lock:
            self._install_steps(new_cfg)
            self._window_us = to_micros(new_cfg.window)
            self._state = dict(
                self._state,
                rem=self._place_replicated(np.asarray(0, np.int64)))

    # _reset is inherited: the base implementation's _sync_period call is a
    # no-op here, and the reset-step dispatch shape is identical.
