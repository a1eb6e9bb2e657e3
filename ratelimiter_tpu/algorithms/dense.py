"""Dense device backend: exact semantics, slot-addressed HBM state.

The TPU answer to "Redis holds a key per user" (reference
``docs/ARCHITECTURE.md:458-469``): state lives in dense int64 columns in
device memory (held as their 32-bit words), one row a key, and the
keyspace directory that maps a key to its row lives there too
(ops/directory.py, ADR-027) — lookup and insertion run inside the
decision step, keyed by the 64-bit id the lane carries (string keys:
the bulk hash of the prefixed key). Every decision
batch is one transfer, one fused jitted call (ops/dense_kernels.py,
``jit_dense_step``) and one fetch, through the hashed and pipelined
surface the sketch backends use (algorithms/hashed_lane.py): the host
holds no key -> slot map and runs no per-key loop. Exactness matches the
oracle bit-for-bit, per 64-bit id; capacity is bounded by the configured
entry count (the sketch backend lifts that bound at the price of
approximation).

Failure semantics (reference ADR-002, ``interface.go:65-69``): any dispatch
failure resolves per Config.fail_open: allow with the fail_open flag set
(the reference swallows the error the same way, ``tokenbucket.go:100-112``)
or raise StorageUnavailableError. Directory exhaustion, the analog of
Redis OOM, is the same failure for the rows it concerns: a row whose key
finds no entry within the probe bound touches no state, is counted
(``directory_stats()["unplaced"]``) and is answered by that policy —
never by another key's row.

Reclaim: an entry idle for two windows equals a fresh one, so a device
pass of its own (``jit_dense_reclaim``) gives such entries up — when a
launch finds the directory nearly full, on ``prune()``, after ``reset``
and at the end of the server's prewarm.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

from ratelimiter_tpu.algorithms.base import RateLimiter
from ratelimiter_tpu.algorithms.hashed_lane import HashedLane
from ratelimiter_tpu.core.clock import Clock, MICROS, to_micros
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.core.errors import StorageUnavailableError
from ratelimiter_tpu.core.types import Algorithm, BatchResult, DispatchTicket
from ratelimiter_tpu.observability import tracing

#: The reclaim gate: two lines of one reckoning. One unplaced row fails
#: its whole dispatch (the policy answers it), so the table is kept
#: ``_MARGIN`` of load under ``directory.unplaced_from(w, pb)``, the
#: lowest load at which a fill leaves a row unplaced: that is the LINE,
#: 0.8188 of the capacity at the server's 128 lanes and bound of 8. A
#: launch whose batch could cross it — every unit of cost in flight and
#: every row of the batch counted as a new key — runs the pass first,
#: whenever the last one was: a table that full is throttled to what
#: expires, not failed (a cold start under a closed loop is served at the
#: host's pace, 3.6 M decisions/s where 2^21 entries hold 3.1 M, and
#: nothing is idle for its first two windows: PERF.md section 6, PR 48).
_MARGIN = 0.05
#: ``_CREST`` under the line is the GATE, 0.79875 there: from it the pass
#: runs at most once in ``_RECLAIM_EVERY`` of a window, so a table whose
#: live keys stand near the gate is not swept on every dispatch. The
#: crest is what one ``_RECLAIM_EVERY`` of first-seen keys adds when a
#: pass frees too little to close the gate again: 1.7 % of 2^21 entries
#: at 2.7 M decisions/s of zipfian 0.99 over 20 M keys, 2.2 % at 3.6 M
#: (replayed).
_CREST = 0.02
_RECLAIM_EVERY = 0.0625


def reclaim_line(w: int, pb: int) -> float:
    """The share of the capacity no launch may cross without a pass, for
    a directory of ``w``-lane buckets probed ``pb`` deep (never under a
    quarter: a table of one-lane buckets is for tests)."""
    from ratelimiter_tpu.ops import directory

    return max(0.25, directory.unplaced_from(w, pb) - _MARGIN)


def reclaim_above(w: int, pb: int) -> float:
    """The gate's share of the capacity: ``_CREST`` under the line."""
    return max(0.25, reclaim_line(w, pb) - _CREST)


class DenseLimiter(HashedLane, RateLimiter):
    _lane_name = "dense"
    _CKPT_KIND = "dense"
    #: The capacity may be a constructor argument the config lacks.
    state_from_config = False

    def __init__(self, config: Config, clock: Optional[Clock] = None,
                 capacity: Optional[int] = None, *, device=None):
        """``device`` pins this limiter's columns, directory, staged
        batches and override table to one ``jax.Device`` — the slice
        seam of the slice-parallel tier (parallel/limiter.build_slices),
        as ``SketchLimiter``'s: every program follows the committed
        state, so N pinned limiters decide on N devices with no traffic
        between them. None keeps the default device, byte for byte."""
        super().__init__(config, clock)
        # Import lazily so the exact backend works without JAX present.
        from ratelimiter_tpu.ops import directory

        self._capacity = int(capacity if capacity is not None
                             else self.config.dense.capacity)
        self._device = device
        self._window_us = to_micros(self.config.window)
        self._install_steps(self.config)
        self._state = self._init_state()
        self._note_resident()
        self._lock = threading.Lock()
        self._init_staging()
        self._injected_failure: Optional[Exception] = None
        # The directory's always-on counts (directory_stats): entries it
        # holds as the host knows them (inserts reported at resolve, less
        # what reclaim and reset freed) and the cumulative sums of the
        # step's tail words.
        self._entries = 0
        self._dir = {"lookups": 0, "probes": 0, "inserts": 0, "unplaced": 0,
                     "reclaimed": 0, "reclaim_passes": 0,
                     "reclaim_seconds": 0.0}
        self._next_reclaim_us = 0
        geo = directory.geometry(self._capacity, self.config.dense.lanes,
                                 self.config.dense.probe_bound)
        self._reclaim_above = reclaim_above(geo["w"], geo["pb"])
        self._reclaim_line = reclaim_line(geo["w"], geo["pb"])
        # Policy engine: overrides resolved in-kernel (binary search over
        # the device-resident table, ops/policy_kernels.py). Entries are
        # re-gated through the same overflow checks as the base config.
        from ratelimiter_tpu.ops.dense_kernels import check_gate_values
        from ratelimiter_tpu.policy import PolicyTable

        self._policy_table = PolicyTable(
            self.config, key_fn=self._policy_key,
            validator=lambda lim, w_us: check_gate_values(lim, w_us),
            window_scaling=True)
        self._policy_dev = None
        self._policy_dev_version = -1

    def _init_state(self):
        """A fresh table, built ON the pinned device (a slice's 2 GB never
        pass through the default one) and committed there."""
        import jax

        from ratelimiter_tpu.ops import dense_kernels

        if self._device is None:
            return dense_kernels.init_directory_state(self.config,
                                                      self._capacity)
        with jax.default_device(self._device):
            state = dense_kernels.init_directory_state(self.config,
                                                       self._capacity)
        return jax.device_put(state, self._device)

    # ------------------------------------------------ compiled programs

    def _install_steps(self, cfg: Config) -> None:
        """Swap in the programs compiled for ``cfg`` (memoized per static
        config). Called with self._lock held, or from __init__. The
        premix step is built lazily (_get_ids_step)."""
        from ratelimiter_tpu.ops import dense_kernels

        self._step = dense_kernels.build_hashed_step(cfg, self._capacity)
        self._ids_step = None
        self._reclaim_step, self._forget_step, self._clear_rem_step = \
            dense_kernels.build_controls(cfg, self._capacity)
        self._fresh = np.asarray(
            dense_kernels.fresh_row(cfg.algorithm, cfg.limit), np.int64)

    def _get_ids_step(self):
        if self._ids_step is None:
            from ratelimiter_tpu.ops import dense_kernels

            self._ids_step = dense_kernels.build_hashed_step(
                self.config, self._capacity, premix=True)
        return self._ids_step

    def _result_format(self) -> tuple:
        from ratelimiter_tpu.ops import dense_kernels

        return dense_kernels.DENSE_ROWS, dense_kernels.unpack_dense

    # The state is 32-bit words on the device — ``cols uint32[2K, C+1]``
    # (ops/dense_kernels.COLUMNS) and the directory's ``dir_lo`` /
    # ``dir_hi`` — and int64 only on the host: the two views below are
    # what a snapshot holds and the one way tests and tools read it.

    def _columns(self) -> np.ndarray:
        """Host view: the state columns, ``int64[K, C+1]``, a row a name
        of ``COLUMNS``."""
        from ratelimiter_tpu.ops.sketch_kernels import join_words

        words = np.asarray(self._state["cols"])
        k = words.shape[0] // 2
        return join_words(words[:k], words[k:])

    def _dir_keys(self) -> np.ndarray:
        """Host view: the directory's keys, ``int64[NB, W]``."""
        from ratelimiter_tpu.ops.sketch_kernels import join_words

        return join_words(np.asarray(self._state["dir_lo"]),
                          np.asarray(self._state["dir_hi"]))

    def _rewrite(self, update, *scalars) -> None:
        """One table-sized control update (ops/dense_kernels.build_rewrite)
        of every slot. Lock must be held."""
        from ratelimiter_tpu.ops.dense_kernels import build_rewrite

        self._state = build_rewrite(self.config.algorithm, update)(
            self._state, *scalars)

    def _policy_key(self, key: str) -> int:
        # The key's directory id, bit-cast: the step searches the table
        # with the id it probes the directory with.
        return int(self._hash([key]).view(np.int64)[0])

    def _policy_limits(self, h64: np.ndarray):
        """Host-side per-request effective limits for result assembly
        (None when no override exists)."""
        if not len(self._policy_table):
            return None
        return self._policy_table.limits_for(
            np.asarray(h64, np.uint64).view(np.int64))

    def _policy_device(self):
        """Device copy of the override table, rebuilt when the host table's
        version moved. Lock must be held."""
        t = self._policy_table
        if self._policy_dev is None or self._policy_dev_version != t.version:
            self._policy_dev = {k: self._place_replicated(v)
                                for k, v in t.host_arrays().items()}
            self._policy_dev_version = t.version
        return self._policy_dev

    def _policy_changed(self, key: str) -> None:
        """Reset the key's refill remainder: it is denominated in the key's
        (old) rate fraction. Forfeits < 1 micro-token, toward denying.
        Lock held by the caller."""
        if self.config.algorithm is not Algorithm.TOKEN_BUCKET:
            return
        self._state, _ = self._clear_rem_step(
            self._state, self._hash([key]), np.ones(1, bool), self._fresh)

    def _apply_config(self, new_cfg: Config) -> None:
        """Dynamic limit: swap in the step compiled for the new limit
        (memoized per config). Window state carries over untouched;
        token-bucket levels shift by the limit delta clamped to
        [0, new_cap] (the consumption-stands contract, see
        exact.ExactLimiter._apply_config) and the pristine row used for
        fresh slots moves to the new full level."""
        from ratelimiter_tpu.ops import dense_kernels

        with self._lock:
            self._install_steps(new_cfg)
            if self.config.algorithm is Algorithm.TOKEN_BUCKET:
                self._rewrite(dense_kernels.shift_tokens,
                              (new_cfg.limit - self.config.limit) * MICROS,
                              new_cfg.limit * MICROS)

    def _apply_window(self, new_cfg: Config) -> None:
        """Dynamic window: slot-state re-bucketing, same contract as the
        exact backend's host migration (exact.ExactLimiter._apply_window
        — consumption stands, re-expiry on the NEW schedule, errs toward
        denying) as ONE fused device update; the new-window step comes
        from the kernel cache (window is part of its key).

        All grid quantities are host scalars, so the migration lowers to
        a handful of elementwise selects over the slot arrays
        (ops/dense_kernels.rebucket_*)."""
        from ratelimiter_tpu.ops import dense_kernels

        W_new = to_micros(new_cfg.window)
        with self._lock:
            # Grid anchors INSIDE the lock: sampling the clock before
            # acquiring it races a concurrent dispatch's window roll, and
            # the migration would then re-bucket against a stale "current
            # window" (over-admission; advisor round-5 finding).
            W_old = self._window_us
            now_us = to_micros(self.clock.now())
            cur_old = (now_us // W_old) * W_old
            p_now = now_us // W_new
            new_start = p_now * W_new
            self._install_steps(new_cfg)
            algo = self.config.algorithm
            if algo is Algorithm.FIXED_WINDOW:
                self._rewrite(dense_kernels.rebucket_fixed,
                              cur_old, new_start)
            elif algo in (Algorithm.SLIDING_WINDOW, Algorithm.TPU_SKETCH):
                # Where the old prev bucket's span ends on the new grid.
                q_prev = (cur_old - 1) // W_new
                self._rewrite(dense_kernels.rebucket_sliding, cur_old,
                              W_old, new_start, q_prev >= p_now,
                              q_prev == p_now - 1)
            else:  # token bucket: the rate changes (baked into the new
                # step), levels and last stand.
                self._rewrite(dense_kernels.clear_rem)
            self._window_us = W_new


    # ------------------------------------------------------------ dispatch
    #
    # Launch and resolve are HashedLane's; below is what the directory
    # adds on either side of the step.

    def _gate_locked(self, b: int, now_us: int) -> Optional[BatchResult]:
        """Run the reclaim pass first when this batch could fill the
        directory: what is in flight (the lane's offered mass, at least
        its rows) and every row of this batch counted as new keys,
        against the gate's share of the capacity (``reclaim_above``) at
        most once in ``_RECLAIM_EVERY`` of a window, against the line's
        (``reclaim_line``) at every launch."""
        filled = self._entries + self._inflight_mass + b
        if (filled > self._reclaim_above * self._capacity
                and (now_us >= self._next_reclaim_us
                     or filled > self._reclaim_line * self._capacity)):
            self._reclaim_locked(now_us)
        return None

    def _step_args(self, slot: np.ndarray, padded: int) -> tuple:
        return (self._state, *self._stage_operands(slot, padded),
                self._policy_device())

    def _tail_words(self, padded: int) -> int:
        from ratelimiter_tpu.ops import directory

        return directory.TAIL_WORDS

    def _note_tail_locked(self, t: DispatchTicket, tails) -> None:
        lookups, probes, inserts, unplaced = (int(x) for x in tails[0])
        d = self._dir
        d["lookups"] += lookups
        d["probes"] += probes
        d["inserts"] += inserts
        d["unplaced"] += unplaced
        self._entries += inserts
        t.unplaced = unplaced

    def _resolve_ticket(self, t: DispatchTicket) -> BatchResult:
        res = super()._resolve_ticket(t)
        if t.unplaced:
            # Rows whose key found no entry: the step gave them no slot
            # (allowed, nothing remaining, as a fail-open answer reads);
            # the policy answers them, as it answers any storage failure.
            if not self.config.fail_open:
                raise StorageUnavailableError(
                    f"dense store full: {t.unplaced} of {t.b} rows found "
                    f"no entry among {self._capacity} "
                    f"(probe bound {self.config.dense.probe_bound}); "
                    "prune idle keys, raise the capacity or use the "
                    "sketch backend")
            res.fail_open = True
        return res

    # ------------------------------------------------------------- reclaim

    def _reclaim_locked(self, now_us: int) -> int:
        """One reclaim pass (ops/dense_kernels._dense_reclaim) on the
        dispatch stream, waited for: entries idle for two windows — the
        TTL analog (SURVEY.md §2.4.9) — are given up. Lock must be held."""
        t0 = tracing.now()
        with tracing.span("reclaim"):
            self._state, freed = self._reclaim_step(
                self._state, np.int64(now_us), self._fresh)
            freed = int(freed)
        # What the launch waited (the span's interval, read here so that
        # it counts with the recorder off): the steps in flight ahead of
        # the pass, the pass, its count's fetch.
        self._dir["reclaim_seconds"] += (tracing.now() - t0) / 1e9
        self._entries -= freed
        self._dir["reclaimed"] += freed
        self._dir["reclaim_passes"] += 1
        self._next_reclaim_us = now_us + int(_RECLAIM_EVERY * self._window_us)
        return freed

    def prune(self, now: Optional[float] = None) -> int:
        t_us = to_micros(self.clock.now() if now is None else float(now))
        with self._lock:
            return self._reclaim_locked(t_us)

    def key_count(self) -> int:
        with self._lock:
            return self._entries

    def directory_stats(self) -> dict:
        """The directory's always-on counts: ``entries`` and ``capacity``
        now; cumulative ``lookups`` (rows), ``probes`` (buckets examined),
        ``inserts`` (keys), ``unplaced`` (rows answered by policy for want
        of an entry) as the steps' results reported them at resolve;
        ``reclaimed`` entries over ``reclaim_passes``, which held their
        launches for ``reclaim_seconds`` in all."""
        with self._lock:
            return dict(self._dir, entries=self._entries,
                        capacity=self._capacity)

    # ----------------------------------------------------------------- reset

    def _reset(self, key: str) -> None:
        """Forget the key: its entry becomes a tombstone and its row
        pristine; the sweep that follows turns the tombstone (and any
        idle entry) back into a free one."""
        with self._lock:
            self._state, found = self._forget_step(
                self._state, self._hash([key]), np.ones(1, bool),
                self._fresh)
            self._entries -= int(found)
            self._reclaim_locked(to_micros(self.clock.now()))

    def _close(self) -> None:
        # State buffers are owned by this limiter; drop the references and
        # let the device allocator reclaim. Shared clocks/meshes are not
        # touched (divergence from reference Close(), SURVEY.md §2.4.13).
        self._state = {}

    # ------------------------------------------------- checkpoint/restore

    def capture_state(self):
        """Lock-held device→host transfer of the state columns and the
        directory's keys, joined ONCE here to the file's format
        (``state_cols int64[K, C+1]``, ``state_dir_keys int64[NB, W]`` —
        what the int64 layout before PR 43 wrote, so snapshots restore
        across it both ways); serialization/writing happen in the
        caller, off-lock. Format/staleness contract:
        ratelimiter_tpu/checkpoint.py."""
        self._check_open()
        with self._lock:
            arrays = {"state_cols": self._columns(),
                      "state_dir_keys": self._dir_keys()}
            arrays.update(self._policy_table.snapshot_arrays())
            extra = {"saved_at": self.clock.now(), "capacity": self._capacity}
        return self._CKPT_KIND, arrays, extra

    def restore(self, path: str) -> None:
        """Replace device state and directory with the snapshot.
        Elapsed-time catch-up is automatic (window roll / token refill key
        off absolute timestamps); keys idle across the gap are reclaimed by
        the usual horizon. A snapshot of another capacity, or of another
        bucket width (``DenseParams.lanes``: an entry's place depends on
        it), is refused."""
        import jax

        from ratelimiter_tpu.checkpoint import load_state
        from ratelimiter_tpu.core.errors import CheckpointError
        from ratelimiter_tpu.ops import dense_kernels, directory

        self._check_open()
        arrays, meta = load_state(path, self._CKPT_KIND, self.config)
        if meta.get("capacity") != self._capacity:
            raise CheckpointError(
                f"{path}: snapshot capacity {meta.get('capacity')} != "
                f"limiter capacity {self._capacity}")
        with self._lock:
            self._policy_table.restore_arrays(arrays)  # pops policy_* columns
        expected = {"state_cols", "state_dir_keys"}
        if set(arrays) != expected:
            raise CheckpointError(
                f"{path}: state arrays {sorted(arrays)} != expected "
                f"{sorted(expected)}")
        keys = arrays["state_dir_keys"]
        if keys.shape != self._state["dir_lo"].shape:
            raise CheckpointError(
                f"{path}: directory of shape {keys.shape} != this "
                f"limiter's {self._state['dir_lo'].shape}")
        # The file's int64 arrays as the device's words, split once here.
        dir_lo, dir_hi = dense_kernels.split_host(keys[None])
        words = {"cols": dense_kernels.split_host(arrays["state_cols"]),
                 "dir_lo": dir_lo, "dir_hi": dir_hi}
        with self._lock:
            self._policy_dev = None
            self._state = {k: jax.device_put(words[k], v.sharding)
                           for k, v in self._state.items()}
            self._entries = int(np.count_nonzero(
                (keys != directory.EMPTY) & (keys != directory.TOMB)))
