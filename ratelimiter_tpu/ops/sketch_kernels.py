"""Count-min-sketch sliding-window kernels — the TPU_SKETCH hot path.

This is the framework's reason to exist (BASELINE.json north star): replace
"one Redis round-trip per key per decision" with "one fused device call per
*batch* against a fixed-size sketch". Key cardinality no longer costs memory
(reference: ~200 B/user in Redis, ``docs/ARCHITECTURE.md:458-469``; here:
depth x width x ring counters TOTAL, shared by all keys) — the cost moves to
a bounded, measured overestimate that can only cause false *denies*, never
over-admission (SURVEY.md §7.4 hard part #3).

Design (SURVEY.md §2.2 sliding-window row, BASELINE config 4):

* The window is covered by ``SW`` sub-windows of ``sub_us`` each. The
  *current* sub-window's counts live in their own ``cur int32[d, w]`` slab;
  completed sub-windows are flushed into a ring ``slabs int32[SW, d, w]``.
  The oldest ring slab is the *boundary* sub-window, weighted by its
  remaining overlap fraction — the same ``prev * (1 - progress)`` shape as
  the exact sliding window (``slidingwindow.go:190-197``), at sub-window
  resolution.
* A running ``totals int32[d, w]`` equals ``cur`` plus all fully-in-window
  ring slabs. Per-step writes touch only ``cur`` and ``totals`` (two
  (d, w) scatter-adds — small, donation-aliased); the full ring is read or
  written ONLY inside a lax.cond that fires once per sub-window rollover
  (the "decay/rotate kernel" of BASELINE config 4), where totals is
  recomputed from the ring masks — a self-healing sweep, not a hot-path
  cost. No Redis TTLs, no full-state traffic per call (hard part #2).
* Row indices use Kirsch-Mitzenmacher double hashing
  ``col_r = (h1 + r * h2) mod w`` so the device only does 32-bit math; the
  host supplies two 32-bit hash halves per key (uint64 emulation avoided on
  the TPU hot path).
* Estimate = min over rows of ``totals + frac * boundary_slab`` (classic CMS
  min-read), clamped >= 0. Admission reuses ops.segment.admit in f32 units,
  segmenting by h1 (a 32-bit segment-id collision merges two keys' in-batch
  sequencing for that batch only — conservative and vanishingly rare).
* Writes are conditional on admission (denial consumes nothing — the
  documented contract the reference's windows violate, SURVEY.md §2.4.2):
  one scatter-add into the current slab and one into totals.

Time is an explicit int64-microsecond scalar operand; everything about
"which sub-window is current / expired" is integer period arithmetic, so
virtual-time tests are exact (SURVEY.md §4.3).
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ratelimiter_tpu.core.clock import to_micros
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.core.errors import InvalidConfigError
from ratelimiter_tpu.ops import ensure_x64, memoized, named, policy_kernels
from ratelimiter_tpu.ops import sortmerge
from ratelimiter_tpu.ops.segment import admit, admit_sorted
from ratelimiter_tpu.ops.sortmerge import row_gather, row_histogram, row_histogram_max

State = Dict[str, jnp.ndarray]

#: slab_period init: far enough in the past that every slab reads as expired.
_NEVER = -(1 << 40)


def sketch_geometry(cfg: Config) -> tuple[int, int, int, int, int]:
    """Returns (window_us, sub_us, SW, S, limit); S == SW is the ring size.

    Fixed-window mode uses a single sub-window (the whole window) and no
    boundary weighting. Sliding mode uses the largest divisor of window_us
    that is <= the requested sketch.sub_windows, so any window duration gets
    an exact integer sub-window size (no fractional-period drift)."""
    from ratelimiter_tpu.core.types import Algorithm

    if cfg.algorithm is Algorithm.TOKEN_BUCKET:
        # Token-bucket semantics live in ops/bucket_kernels.py (decaying
        # debt meter, no sub-window ring); building windowed kernels for a
        # TOKEN_BUCKET config would silently change semantics.
        raise InvalidConfigError(
            "token bucket uses bucket_kernels, not the windowed sketch "
            "(construct via create_limiter or SketchTokenBucketLimiter)")
    if cfg.limit >= (1 << 24):
        # The sketch admission path compares f32 quantities; limits at or
        # above 2^24 would make boundary comparisons inexact (ops/segment
        # _segment_exclusive_cumsum_exact_f32's cast argument). Use the
        # dense backend for limits that large.
        raise InvalidConfigError(
            f"sketch backend requires limit < 2**24, got {cfg.limit}")
    W = to_micros(cfg.window)
    if cfg.algorithm is Algorithm.FIXED_WINDOW:
        SW = 1
    else:
        SW = next(k for k in range(min(cfg.sketch.sub_windows, W), 0, -1)
                  if W % k == 0)
    return W, W // SW, SW, SW, cfg.limit


def init_state(cfg: Config) -> State:
    ensure_x64()
    _, _, _, S, _ = sketch_geometry(cfg)
    d, w = cfg.sketch.depth, cfg.sketch.width
    state = {
        "cur": jnp.zeros((d, w), jnp.int32),
        "slabs": jnp.zeros((S, d, w), jnp.int32),
        "totals": jnp.zeros((d, w), jnp.int32),
        "slab_period": jnp.full((S,), _NEVER, jnp.int64),
        "last_period": jnp.asarray(_NEVER, jnp.int64),
    }
    T = cfg.hierarchy.tenants
    if T:
        # Hierarchical cascade (ADR-020): per-tenant + global in-window
        # counters riding the SAME sub-window ring clock as the CMS —
        # one extra (S, T+1) slab, flushed/recomputed by the same
        # rollover sweep. Index T is the global scope.
        state.update({
            "tn_cur": jnp.zeros((T + 1,), jnp.int32),
            "tn_slabs": jnp.zeros((S, T + 1), jnp.int32),
            "tn_totals": jnp.zeros((T + 1,), jnp.int32),
        })
    K = cfg.sketch.hh_slots
    if K:
        # Heavy-hitter side table: direct-mapped (slot = h1 mod K) private
        # ring cells for promoted keys, sharing the sketch's period clock.
        # A key with h1 == 0 can never own a slot (0 marks free) — a
        # 2^-32 event whose only effect is staying on the sketch path.
        state.update({
            "hh_owner": jnp.zeros((K,), jnp.uint32),
            # The owner's SECOND hash half, captured at claim time: the
            # DCN exporter needs the full (h1, h2) pair to fold a
            # promoted key's private counts back into CMS-column form on
            # the wire (parallel/dcn.export_completed).
            "hh_owner2": jnp.zeros((K,), jnp.uint32),
            "hh_cur": jnp.zeros((K,), jnp.int32),
            "hh_slabs": jnp.zeros((S, K), jnp.int32),
            "hh_totals": jnp.zeros((K,), jnp.int32),
            "hh_last": jnp.full((K,), _NEVER, jnp.int64),
        })
    return state


@jax.named_scope("rotate")
def _rollover(state: State, p, *, SW: int, S: int) -> State:
    """Advance state to period p (p > last_period). Flushes ``cur`` into the
    ring at slot ``last_period % S``, recomputes ``totals`` as the masked sum
    of ring slabs still fully inside the window (self-healing — any
    transient negatives from reset subtraction wash out), and zeroes
    ``cur``.

    This is deliberately NOT part of the per-request step kernel: a
    lax.cond over the ring would force XLA to materialize copies of the
    full (S, d, w) state every step (measured ~1.4 ms/step at 60x4x64K).
    The period is pure integer arithmetic on the host-supplied timestamp,
    so the *host* decides when to dispatch this kernel (~once per
    sub-window), exactly like it decides when to dispatch steps. See
    SketchLimiter._sync_period.
    """
    p_old = state["last_period"]
    slabs, periods = state["slabs"], state["slab_period"]
    slot = (p_old % S).astype(jnp.int32)
    slabs = slabs.at[slot].set(state["cur"])
    periods = periods.at[slot].set(p_old)
    # Fully-in-window flushed periods: [p-SW+1, p-1]. (The boundary period
    # p-SW is read weighted at estimate time; period p is `cur`.)
    in_window = (periods >= p - SW + 1) & (periods <= p - 1)
    totals = jnp.tensordot(in_window.astype(jnp.int32), slabs, axes=1)
    out = {"cur": jnp.zeros_like(state["cur"]), "slabs": slabs,
           "totals": totals, "slab_period": periods,
           "last_period": jnp.asarray(p, jnp.int64)}
    if "tn_cur" in state:
        # Tenant/global counters share the ring clock (ADR-020): same
        # flush + masked-sum recompute as the CMS and hh slabs.
        tn_slabs = state["tn_slabs"].at[slot].set(state["tn_cur"])
        out.update({
            "tn_cur": jnp.zeros_like(state["tn_cur"]),
            "tn_slabs": tn_slabs,
            "tn_totals": jnp.tensordot(in_window.astype(jnp.int32),
                                       tn_slabs, axes=1),
        })
    if "hh_owner" in state:
        # The side table rides the same period clock: flush, recompute,
        # and reclaim slots idle a full window (their in-window counts are
        # provably zero — every write at period q lives in slab q, and
        # idleness means no q > p - SW).
        hh_slabs = state["hh_slabs"].at[slot].set(state["hh_cur"])
        hh_totals = jnp.tensordot(in_window.astype(jnp.int32), hh_slabs,
                                  axes=1)
        idle = state["hh_last"] <= p - SW
        out.update({
            "hh_owner": jnp.where(idle, jnp.uint32(0), state["hh_owner"]),
            "hh_owner2": jnp.where(idle, jnp.uint32(0),
                                   state["hh_owner2"]),
            "hh_cur": jnp.zeros_like(state["hh_cur"]),
            "hh_slabs": hh_slabs,
            "hh_totals": hh_totals,
            "hh_last": state["hh_last"],
        })
    return out


def _columns(h1, h2, d: int, w: int):
    """Kirsch-Mitzenmacher double-hashed CMS columns, (B, d) int32 column
    indices into each of the d rows."""
    r = jnp.arange(d, dtype=jnp.uint32)
    cols = (h1[:, None] + r[None, :] * h2[:, None]) & jnp.uint32(w - 1)
    return cols.astype(jnp.int32)


def _boundary_weight(state: State, p, now_us, *, sub_us: int, SW: int,
                     S: int, weighted: bool):
    """(frac, boundary) for the sliding-window boundary sub-window: the
    rollover-boundary check (is the slab at slot p % S the period p-SW
    slab?) and its remaining-overlap weight; fixed-window mode returns
    (0.0, None)."""
    if not weighted:
        return jnp.float32(0.0), None
    # Ring size S == SW, so the boundary period p-SW lives at
    # slot p % S (the very slot the next rollover overwrites).
    b_idx = (p % S).astype(jnp.int32)
    boundary_valid = state["slab_period"][b_idx] == p - SW
    elapsed_in = (now_us - p * sub_us).astype(jnp.float32)
    frac = jnp.where(
        boundary_valid,
        jnp.clip(1.0 - elapsed_in / jnp.float32(sub_us), 0.0, 1.0),
        0.0)
    boundary = jax.lax.dynamic_index_in_dim(state["slabs"], b_idx,
                                            keepdims=False)
    return frac, boundary


def _estimate(state: State, cols, p, now_us, *, sub_us: int, SW: int, S: int,
              weighted: bool = True, runs=None):
    """Min-over-rows window estimate at the given (B, d) columns, via
    sort-merge reads (ops/sortmerge.py — no gathers on the hot path).
    ``weighted`` adds the boundary sub-window scaled by its remaining
    overlap fraction (sliding semantics); fixed-window mode reads totals
    alone. With ``runs`` (a batch cut into runs of equal keys, direct
    indexing regime) ``cols`` is not read: the table is read once a run
    and the estimate comes back in the runs' sorted order.

    Returns (est, frac, boundary): the (B,) min-estimate plus the scalar
    boundary weight and the dense (d, w) boundary slab (None when not
    weighted) so the conservative-update write path can reuse them."""
    from ratelimiter_tpu.ops.sortmerge import _use_sortmerge

    d, w = state["totals"].shape
    if runs is not None:
        frac, boundary = _boundary_weight(state, p, now_us, sub_us=sub_us,
                                          SW=SW, S=S, weighted=weighted)
        rows = tuple(state["totals"][r] for r in range(d))
        if weighted:
            # The dense pre-combination of the per-row branch below, a
            # row at a time.
            rows = tuple(t_r.astype(jnp.float32)
                         + frac * boundary[r].astype(jnp.float32)
                         for r, t_r in enumerate(rows))
        return _run_estimate(rows, runs, w), frac, boundary
    B = cols.shape[0]
    if weighted:
        frac, boundary = _boundary_weight(state, p, now_us, sub_us=sub_us,
                                          SW=SW, S=S, weighted=True)
        if not _use_sortmerge(B, w):
            # Direct-indexing regime: pre-combine the two tables DENSELY
            # (frac is a scalar) and gather once per row. Numerically
            # identical to gathering both and combining per element, but
            # far faster on the TPU at the serving shape (B=4096,
            # w=65536; ADR-004, not measured on this round's code) — XLA
            # lowers the fused two-gather combine pathologically.
            combined = (state["totals"].astype(jnp.float32)
                        + frac * boundary.astype(jnp.float32))
            est = None
            for r in range(d):
                e_r = combined[r][cols[:, r]]
                est = e_r if est is None else jnp.minimum(est, e_r)
        else:
            # Sort-merge regime (B >= w/2): delta encoding needs integer
            # rows for exactness, so gather both and combine after.
            est = None
            for r in range(d):
                t_r, b_r = row_gather((state["totals"][r], boundary[r]),
                                      cols[:, r])
                e_r = t_r.astype(jnp.float32) + frac * b_r.astype(jnp.float32)
                est = e_r if est is None else jnp.minimum(est, e_r)
    else:
        frac, boundary = jnp.float32(0.0), None
        est = None
        for r in range(d):
            (t_r,) = row_gather((state["totals"][r],), cols[:, r])
            e_r = t_r.astype(jnp.float32)
            est = e_r if est is None else jnp.minimum(est, e_r)
    return jnp.maximum(est, 0.0), frac, boundary  # (B,), scalar, (d, w)|None


# ------------------------------------- one table access a run of equal keys
#
# On the programs ``sortmerge._use_run_dedup`` names, the step sorts the
# batch by h1 FIRST (the sort ``admit`` would make), cuts the sorted rows
# into runs of equal (h1, h2) and makes its d gathers and d scatters once
# per run, over the compacted list of run heads, in chunks of
# ``sortmerge.run_chunk(B)`` with a data-dependent trip count: what the
# table accesses cost follows the runs a dispatch holds, not its padded
# rows (ops/sortmerge.py has the plumbing and the numbers). Nothing is
# approximate: a run's rows read the same d cells, a scatter-max keeps
# the run's maximum and an integer scatter-add the run's sum, so every
# decision and every state cell is the per-row body's, bit for bit
# (tests/test_run_dedup.py).


class _Runs(NamedTuple):
    """A batch in h1-sorted order (stable: batch order inside an h1
    segment), cut into runs of equal (h1, h2)."""
    h1: jnp.ndarray        # uint32[B], sorted
    h2: jnp.ndarray        # uint32[B], carried
    n: jnp.ndarray         # int32[B], carried
    orig: jnp.ndarray      # int32[B]: the row's index in the batch
    seg_head: jnp.ndarray  # bool[B]: first row of its h1 segment (admit's)
    head: jnp.ndarray      # bool[B]: first row of its (h1, h2) run
    count: jnp.ndarray     # int32[]: runs in the batch
    key: jnp.ndarray       # int32[B]: sortmerge.heads_first's key
    h1c: jnp.ndarray       # uint32[Bp]: the run heads' halves first, in
    h2c: jnp.ndarray       # sorted order, zero-padded to whole chunks
    chunk: int             # static: heads an access loop visits at a time


def _chunk_pad(x, C: int):
    return jnp.pad(x, (0, -x.shape[0] % C))


@jax.named_scope("run_sort")
def _sorted_runs(h1, h2, n) -> _Runs:
    B = h1.shape[0]
    sid = jax.lax.bitcast_convert_type(h1, jnp.int32)
    s_sid, s_h2, s_n, orig = jax.lax.sort(
        (sid, h2, n, jax.lax.iota(jnp.int32, B)), num_keys=1, is_stable=True)
    s_h1 = jax.lax.bitcast_convert_type(s_sid, jnp.uint32)
    seg_head = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), s_sid[1:] != s_sid[:-1]])
    head = sortmerge.run_heads(seg_head, s_h2)
    key, h1c, h2c = sortmerge.heads_first(head, s_h1, s_h2)
    C = sortmerge.run_chunk(B)
    return _Runs(s_h1, s_h2, s_n, orig, seg_head, head,
                 jnp.sum(head, dtype=jnp.int32), key,
                 _chunk_pad(h1c, C), _chunk_pad(h2c, C), C)


def _run_chunks(runs: _Runs, d: int, w: int, carry, visit):
    """``carry`` after ``visit(carry, cols, at, start)`` over every chunk
    of run heads that holds one: ``cols`` the chunk's (C, d) columns,
    ``at(x)`` the chunk's slice of a compact array, ``start`` its offset.
    ceil(count / C) iterations."""
    C = runs.chunk

    def body(k, carry):
        at = lambda x: jax.lax.dynamic_slice_in_dim(x, k * C, C)
        return visit(carry, _columns(at(runs.h1c), at(runs.h2c), d, w),
                     at, k * C)

    return jax.lax.fori_loop(0, (runs.count + (C - 1)) // C, body, carry)


@jax.named_scope("run_gather")
def _run_estimate(rows, runs: _Runs, w: int):
    """f32[B], sorted order: min over the d ``rows`` of ``row[col_r]``,
    read once per run and spread to the run's rows. ``rows`` are d
    separate (w,) arrays: a row sliced off a (d, w) table inside the loop
    is a copy of the row every iteration (22 us each at w = 2**20)."""
    d = len(rows)

    def visit(est_c, cols, at, start):
        est = None
        for r, row in enumerate(rows):
            e_r = row[cols[:, r]].astype(jnp.float32)
            est = e_r if est is None else jnp.minimum(est, e_r)
        return jax.lax.dynamic_update_slice_in_dim(est_c, est, start, 0)

    est_c = _run_chunks(runs, d, w,
                        jnp.zeros(runs.h1c.shape, jnp.float32), visit)
    return sortmerge.spread_heads(runs.key, jnp.maximum(est_c, 0.0),
                                  runs.count)


@jax.named_scope("run_scatter")
def _run_write(vals_c, runs: _Runs, d: int, w: int, how: str):
    """d dense (w,) rows: ``zeros.at[col_r].max`` (or ``.add``) of one
    value a run — ``vals_c`` in heads-first order, neutral (zero) from
    ``runs.count`` on."""
    vals_c = _chunk_pad(vals_c, runs.chunk)

    def visit(rows, cols, at, _start):
        v = at(vals_c)
        return tuple(getattr(row.at[cols[:, r]], how)(v)
                     for r, row in enumerate(rows))

    zeros = tuple(jnp.zeros((w,), vals_c.dtype) for _ in range(d))
    return _run_chunks(runs, d, w, zeros, visit)


def _hh_boundary_slab(state: State, p, *, SW: int, S: int):
    """The side table's boundary sub-window column vector (K,). Validity is
    carried by ``frac`` (0 when the boundary period is absent), exactly as
    for the CMS boundary slab."""
    b_idx = (p % S).astype(jnp.int32)
    return jax.lax.dynamic_index_in_dim(state["hh_slabs"], b_idx,
                                        keepdims=False)


def _sketch_step(state: State, h1, h2, n, now_us, policy=None, hier=None, *,
                 limit: int, sub_us: int, SW: int, S: int, d: int, w: int,
                 iters: int, weighted: bool, conservative: bool,
                 hh: int = 0, hh_thresh: float = 0.0, tenants: int = 0,
                 axis_name: str | None = None, counted: bool = False):
    """One batch against the windowed sketch: ``(state, (allowed,
    remaining, est))`` in batch order. ``counted`` adds a third element,
    the table-access runs of this batch (int32 scalar) on the programs
    that access the table once a run, None on the others."""
    # Precondition (host-enforced via _sync_period): state.last_period is
    # the period of now_us. Clamp defends against clock skew backwards —
    # the reference has the same NTP caveat (``docs/ALGORITHMS.md:162``).
    now_us = jnp.maximum(now_us, state["last_period"] * sub_us)
    p = state["last_period"]

    # One table access a run of equal keys (section above): the batch is
    # sorted here, once, for the accesses AND the admission, and the body
    # below runs on the sorted rows — everything per row in it is either
    # elementwise or an order-free scatter — until its three results are
    # sorted back. The cascade sequences rows of DIFFERENT keys in batch
    # order, so with it the body keeps the batch's order and only the
    # accesses take the sorted one (two more sorts; no cell enables it).
    runs = in_batch_order = to_sorted = None
    if sortmerge._use_run_dedup(h1.shape[0], w):
        runs = _sorted_runs(h1, h2, n)
        in_batch_order = bool(tenants) and hier is not None
        if not in_batch_order:
            h1, h2, n = runs.h1, runs.h2, runs.n

    with jax.named_scope("estimate"):
        cols = None if runs is not None else _columns(h1, h2, d, w)  # (B, d)
        est, frac, boundary = _estimate(state, cols, p, now_us,
                                        sub_us=sub_us, SW=SW, S=S,
                                        weighted=weighted, runs=runs)
        if in_batch_order:
            _, est, rank = jax.lax.sort(
                (runs.orig, est, jax.lax.iota(jnp.int32, est.shape[0])),
                num_keys=1, is_stable=False)
            to_sorted = lambda x: jax.lax.sort(
                (rank, x), num_keys=1, is_stable=False)[1]

    if hh:
        # Heavy-hitter side table (ROADMAP v0.2): a promoted key's NEW
        # traffic is counted exactly in its private ring cell while its
        # pre-promotion history stays in the sketch and expires on the
        # normal window schedule — the estimate is the SUM of the two.
        # Nothing is copied at promotion (a copied estimate would freeze
        # the key's most-inflated moment — promotion fires exactly when
        # est crosses the threshold — into a window-long sentence), and
        # nothing is counted twice (each request lives either in the
        # sketch or in the private cell, never both). Direct-mapped:
        # slot = h1 mod K, identity = h1 (a 32-bit identity collision
        # merges two keys into one exact cell — same direction as a CMS
        # collision: over-count, false denies only).
        sid_hh = jax.lax.bitcast_convert_type(
            h1 & jnp.uint32(hh - 1), jnp.int32)
        owner = state["hh_owner"][sid_hh]                    # (B,)
        mine = owner == h1
        est_hh = state["hh_totals"][sid_hh].astype(jnp.float32)
        if weighted:
            hh_b = _hh_boundary_slab(state, p, SW=SW, S=S)
            est_hh = est_hh + frac * hh_b[sid_hh].astype(jnp.float32)
        est = est + jnp.where(mine, jnp.maximum(est_hh, 0.0), 0.0)
    else:
        mine = None

    with jax.named_scope("admit"):
        if policy is not None:
            # Per-key limit overrides (policy engine): the search key is the
            # device-side packing of the (h1, h2) halves the columns already
            # ride on, so the lookup needs no extra operand — but it is
            # log2(capacity) + 2 serialized int64 gathers a row, more than
            # the d gathers + d scatters of the rule itself, so it runs only
            # when the table holds an entry (limit_for_rows). Limits are
            # validated < 2^24 at override-set time (the same f32-exactness
            # gate as the base limit).
            lim_f = policy_kernels.limit_for_rows(
                policy, h1, h2, limit, jnp.float32)
        else:
            lim_f = jnp.float32(limit)
        avail = jnp.maximum(lim_f - est, 0.0)
        n_f = n.astype(jnp.float32)
        if runs is not None and not in_batch_order:
            allowed, seen = admit_sorted(n_f, avail, runs.seg_head, iters)
        else:
            sid = jax.lax.bitcast_convert_type(h1, jnp.int32)
            allowed, seen, _ = admit(sid, n_f, avail, iters)

    tn_hist = None
    if tenants and hier is not None:
        # Hierarchical cascade (ADR-020): key-scope survivors run the
        # tenant + global stages against the tn counter slab, still in
        # THIS dispatch. The tenant boundary sub-window rides the same
        # frac scalar as the CMS boundary (frac is 0 when the boundary
        # period is absent); its fractional part ceils — conservative,
        # toward denying — so tenant/global admission stays exact int64.
        from ratelimiter_tpu.ops import hier_kernels
        from ratelimiter_tpu.ops.segment import segment_consumption

        tid = hier_kernels.derive_tids(hier, h1, h2, tenants)
        est_tn = state["tn_totals"].astype(jnp.int64)
        if weighted:
            tn_b = jax.lax.dynamic_index_in_dim(
                state["tn_slabs"], (p % S).astype(jnp.int32),
                keepdims=False)
            est_tn = est_tn + jnp.ceil(
                frac * jnp.maximum(tn_b, 0).astype(jnp.float32)
            ).astype(jnp.int64)
        avail_sc = hier_kernels.scope_avail(hier["limit"],
                                            jnp.maximum(est_tn, 0))
        allowed_casc, tn_hist = hier_kernels.cascade_admit(
            allowed, tid, n, avail_sc, hier["weight"], tenants, iters)
        # All-or-nothing: recompute the key scope's consumption view
        # under the FINAL mask so writes (CU targets / adds), hh
        # promotion targets, and the reported remaining all reflect
        # only what was actually admitted. Cond'd on the cascade having
        # flipped any verdict: under no tenant/global contention (the
        # common case) the masks are equal and the stage-1 view already
        # IS the final view — the extra sort pass is skipped.
        seen = jax.lax.cond(
            jnp.any(allowed_casc != allowed),
            lambda: avail - segment_consumption(
                sid, jnp.where(allowed_casc, n_f, jnp.float32(0.0))),
            lambda: seen)
        allowed = allowed_casc
        if axis_name is not None:
            tn_hist = jax.lax.psum(tn_hist, axis_name)
    not_mine = True if mine is None else ~mine

    with jax.named_scope("write_back"):
        if conservative and axis_name is None:
            # Conservative update (SURVEY.md hard part #3): raise each touched
            # cell only as high as the largest single-key post-batch target that
            # maps to it, never the sum of colliding keys. Target for a key's
            # last allowed request is est + total in-batch consumption; the
            # per-column segment-max picks exactly that. Denied requests write
            # nothing (matching "denial consumes nothing").
            #
            # CU requires a globally-sequenced view of the batch, so it applies
            # on single-chip and mesh-gather paths only. Under the delta merge
            # (axis_name set) the else-branch's psum-of-increments runs instead:
            # a pmax of per-chip CU targets would UNDERCOUNT cross-chip traffic
            # (true counts add across chips) and a psum of per-chip CU deltas
            # can undercount rows whose dense read exceeds the min-estimate —
            # both break the never-over-admit direction. Vanilla sums never do.
            target = jnp.where(allowed & not_mine, est + (avail - seen) + n_f, 0.0)
            if runs is not None:
                if in_batch_order:
                    target = to_sorted(target)
                maxima = _run_write(
                    sortmerge.run_maxima_first(target, runs.head, runs.count),
                    runs, d, w, "max")
            deltas = []
            for r in range(d):
                m_r = (maxima[r] if runs is not None
                       else row_histogram_max(cols[:, r], target, w))
                read_r = state["totals"][r].astype(jnp.float32)
                if boundary is not None:
                    read_r = read_r + frac * boundary[r].astype(jnp.float32)
                deltas.append(jnp.ceil(jnp.maximum(m_r - read_r, 0.0)))
            hists = jnp.stack(deltas).astype(jnp.int32)
            totals = state["totals"] + hists
            cur = state["cur"] + hists
        else:
            add = jnp.where(allowed & not_mine, n, 0).astype(jnp.int32)  # (B,)
            if runs is not None:
                if in_batch_order:
                    add = to_sorted(add)
                hists = jnp.stack(_run_write(
                    sortmerge.run_sums_first(add, runs.head, runs.count),
                    runs, d, w, "add"))
            else:
                hists = jnp.stack([row_histogram(cols[:, r], add, w)
                                   for r in range(d)])
            if axis_name is not None:
                # Multi-chip delta merge: every chip adds the summed
                # histogram, keeping the replicated-state invariant (ICI
                # psum — the analog of all app servers sharing one Redis,
                # SURVEY.md §2.6).
                hists = jax.lax.psum(hists, axis_name)
            totals = state["totals"] + hists
            cur = state["cur"] + hists
    # cur and totals share the same histogram so the "current sub-window
    # also counts in totals" invariant holds by construction.

    new_state = {"cur": cur, "slabs": state["slabs"], "totals": totals,
                 "slab_period": state["slab_period"],
                 "last_period": state["last_period"]}

    if "tn_cur" in state:
        if tn_hist is not None:
            th = tn_hist.astype(jnp.int32)
            new_state.update({"tn_cur": state["tn_cur"] + th,
                              "tn_slabs": state["tn_slabs"],
                              "tn_totals": state["tn_totals"] + th})
        else:
            # Hierarchy-shaped state on a path that did not receive the
            # table operand (reset-adjacent internal calls): counters
            # carry through untouched.
            new_state.update({k: state[k] for k in
                              ("tn_cur", "tn_slabs", "tn_totals")})

    if hh:
        # Owned-key consumption goes to the private cells (exact counts).
        n_add = jnp.where(allowed & mine, n, 0).astype(jnp.int32)
        hh_hist = row_histogram(sid_hh, n_add, hh)
        # Promotion: unowned keys whose post-batch target crosses the
        # threshold claim their (free) slot — ownership only, no mass
        # (see the estimate comment above). Winner selection packs
        # (target, h1) into one int64 scatter-max so the slot goes to the
        # HOTTEST candidate deterministically (incl. across chips).
        target_pr = jnp.where(allowed, est + (avail - seen) + n_f, est)
        free = owner == jnp.uint32(0)
        cand = not_mine & free & (target_pr >= jnp.float32(hh_thresh))
        mass_i = jnp.ceil(jnp.clip(target_pr, 0.0, float(1 << 30))
                          ).astype(jnp.int64)
        packed = jnp.where(cand,
                           (mass_i << 32) | h1.astype(jnp.int64),
                           jnp.int64(0))
        touched = row_histogram(sid_hh, (mine | cand).astype(jnp.int32),
                                hh) > 0
        claims = jnp.zeros((hh,), jnp.int64).at[sid_hh].max(packed)
        if axis_name is not None:
            hh_hist = jax.lax.psum(hh_hist, axis_name)
            # Packed max is order-consistent across chips: the global max
            # target (ties broken by h1) wins everywhere.
            claims = jax.lax.pmax(claims, axis_name)
            touched = jax.lax.pmax(touched, axis_name)
        # Winner's h2, recovered by a second scatter keyed on the winning
        # packed value (equal packed => equal h1 => same key => same h2,
        # so ties cannot mix pairs). Needed so DCN export can rebuild the
        # owner's CMS columns (export_completed).
        winner = cand & (packed == claims[sid_hh])
        h2w = jnp.zeros((hh,), jnp.uint32).at[sid_hh].max(
            jnp.where(winner, h2, jnp.uint32(0)))
        if axis_name is not None:
            h2w = jax.lax.pmax(h2w, axis_name)
        claim_owner = (claims & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        newly = (state["hh_owner"] == jnp.uint32(0)) & (
            claim_owner != jnp.uint32(0))
        new_state.update({
            "hh_owner": jnp.where(newly, claim_owner, state["hh_owner"]),
            "hh_owner2": jnp.where(newly, h2w, state["hh_owner2"]),
            "hh_cur": state["hh_cur"] + hh_hist,
            "hh_slabs": state["hh_slabs"],
            "hh_totals": state["hh_totals"] + hh_hist,
            "hh_last": jnp.where(touched, p, state["hh_last"]),
        })

    remaining = jnp.maximum(
        jnp.floor(seen - jnp.where(allowed, n_f, 0.0)), 0.0).astype(jnp.int32)
    if runs is not None and not in_batch_order:
        with jax.named_scope("run_unsort"):
            _, allowed, remaining, est = jax.lax.sort(
                (runs.orig, allowed.astype(jnp.int32), remaining, est),
                num_keys=1, is_stable=False)
            allowed = allowed.astype(bool)
    outs = (allowed, remaining, est)
    if counted:
        return new_state, outs, None if runs is None else runs.count
    return new_state, outs


def _sketch_reset(state: State, h1, h2, now_us, *,
                  sub_us: int, SW: int, S: int, d: int, w: int,
                  weighted: bool, hh: int = 0):
    """Per-key reset: subtract the key's current min-estimate from all its
    cells in both ``cur`` and ``totals`` (equal amounts; cells may go
    transiently negative, reads clamp at 0 and the next rollover's totals
    recompute self-heals). Colliding keys gain allowance — errors toward
    allowing, never toward false denial. Promoted keys subtract from their
    private side-table cells instead."""
    now_us = jnp.maximum(now_us, state["last_period"] * sub_us)
    p = state["last_period"]
    cols = _columns(h1, h2, d, w)
    est, frac, _ = _estimate(state, cols, p, now_us, sub_us=sub_us, SW=SW,
                             S=S, weighted=weighted)
    if hh:
        # A promoted key's estimate is CMS remnant + private count
        # (_sketch_step): reset subtracts each part from its own table.
        sid_hh = jax.lax.bitcast_convert_type(
            h1 & jnp.uint32(hh - 1), jnp.int32)
        mine = state["hh_owner"][sid_hh] == h1
        est_hh = state["hh_totals"][sid_hh].astype(jnp.float32)
        if weighted:
            hh_b = _hh_boundary_slab(state, p, SW=SW, S=S)
            est_hh = est_hh + frac * hh_b[sid_hh].astype(jnp.float32)
        sub_hh = jnp.where(mine, jnp.floor(jnp.maximum(est_hh, 0.0)),
                           0.0).astype(jnp.int32)
        hh_hist = row_histogram(sid_hh, sub_hh, hh)
        sub = jnp.floor(est).astype(jnp.int32)
    else:
        hh_hist = None
        sub = jnp.floor(est).astype(jnp.int32)
    hists = jnp.stack([row_histogram(cols[:, r], sub, w) for r in range(d)])
    out = {"cur": state["cur"] - hists, "slabs": state["slabs"],
           "totals": state["totals"] - hists,
           "slab_period": state["slab_period"],
           "last_period": state["last_period"]}
    if "tn_cur" in state:
        # Reset forgives a KEY's usage only: tenant/global counters track
        # actually-admitted aggregate traffic and deliberately stand
        # (ADR-020 — subtracting one key's estimate from its tenant would
        # let a reset-hammering key drain its whole tenant's accounting).
        out.update({k: state[k] for k in
                    ("tn_cur", "tn_slabs", "tn_totals")})
    if hh:
        out.update({
            "hh_owner": state["hh_owner"],
            "hh_owner2": state["hh_owner2"],
            "hh_cur": state["hh_cur"] - hh_hist,
            "hh_slabs": state["hh_slabs"],
            "hh_totals": state["hh_totals"] - hh_hist,
            "hh_last": state["hh_last"],
        })
    return out


# ------------------------------------------------- the packed result
#
# What leaves the device per dispatch is ONE int32 buffer per device and
# nothing else: rows of P words each, ``[row 0 (P) | row 1 (P) | ...]``,
# row 0 the allow bit, row 1 ``remaining``; the debt sketch adds two
# rows (bucket_kernels.pack_bucket). Every 64-bit or floating column of
# BatchResult is rebuilt on the host from these integers and the
# ticket's own ``now_us`` (unpack_window / bucket_kernels.unpack_bucket)
# in IEEE float64 — a v5e has no 64-bit vectors, so an int64 or float64
# output is two 32-bit buffers the host re-joins at every fetch, and its
# float64 division is emulated (ADR-010 addendum 2). Under a mesh the
# buffer is sharded by batch: every device holds the same rows over its
# own P/n keys (plus, under the collective router, a tail of its own —
# ops/route_kernels.py), and ``result_rows`` lays the shards side by
# side again.

#: Rows of the windowed rules' packed result: allowed, remaining.
WINDOW_ROWS = 2

#: Tail words of the serving step's packed result on the programs that
#: access the table once a run of equal keys (sortmerge._use_run_dedup):
#: the padded rows the step decided — the accesses a table row the
#: per-row body makes — and the runs it accessed instead.
RUN_TAIL_WORDS = 2


def step_tail_words(padded: int, width: int) -> int:
    """Tail words of ``build_hashed_step``'s result for a padded batch:
    read off the same static predicate the step's body reads."""
    return RUN_TAIL_WORDS if sortmerge._use_run_dedup(padded, width) else 0


def pack_rows(*rows):
    """``int32[len(rows) * P]``: the rows one after the other, each
    narrowed (or widened) to int32. The one concatenation both rules'
    packers end with."""
    return jnp.concatenate([r.astype(jnp.int32) for r in rows])


def split_words(x):
    """int64 -> its (low, high) 32-bit words as int32, by a mask and a
    shift: a ``bitcast_convert_type`` on a 64-bit type is what the TPU's
    X64 rewriter refuses (PERF.md §5)."""
    low = jax.lax.bitcast_convert_type(
        (x & 0xFFFFFFFF).astype(jnp.uint32), jnp.int32)
    return low, (x >> 32).astype(jnp.int32)


def join_words(low: np.ndarray, high: np.ndarray) -> np.ndarray:
    """Host inverse of split_words: int64 from int32 word arrays."""
    return (high.astype(np.int64) << 32) | low.astype(np.uint32)


def pack_window(allowed, remaining):
    """The windowed sketches' (sliding and fixed) result as it leaves the
    device: ``int32[2P] = [allowed(P) | remaining(P)]``. A plain traced
    function, the ONE definition of this format: the serving step
    (build_hashed_step), the replicated mesh's (parallel/mesh_kernels.py)
    and the collective router's (ops/route_kernels.py) all end with it.
    ``remaining`` is the step's own int32, in ``[0, limit_k]`` where
    ``limit_k`` is the config limit or the key's override — both < 2**24
    (check_gates, SketchLimiter._policy_validate) — so one word holds
    it. No retry or reset word is shipped: both are functions of
    ``now_us`` and the allow bit alone (unpack_window)."""
    return pack_rows(allowed, remaining)


def result_rows(words: np.ndarray, rows: int, *, shards: int = 1,
                tail: int = 0):
    """Host side of pack_rows: the fetched ``int32[shards * (rows * Pl +
    tail)]`` as ``(int32[rows, shards * Pl], int32[shards, tail])`` —
    the rows in batch order over all shards, and each shard's tail words.
    One shard: both are views of the fetch."""
    per = words.shape[0] // shards
    pl = (per - tail) // rows
    by_shard = words.reshape(shards, per)
    body = by_shard[:, :rows * pl].reshape(shards, rows, pl)
    return (body.transpose(1, 0, 2).reshape(rows, shards * pl),
            by_shard[:, rows * pl:])


def unpack_window(rows: np.ndarray, b: int, now_us: int, window_us: int):
    """BatchResult's four columns from pack_window's rows, on the host:
    ``(allowed bool[b], remaining int64[b], retry_after f64[b], reset_at
    f64[b])``. Retry-after is time to window reset
    (``fixedwindow.go:107-112``): the arithmetic the step itself ran
    until PR 29, now in IEEE float64 on two Python integers — ``now_us``
    is the dispatch's own timestamp (the ticket's), not the step's
    skew-clamped copy."""
    allowed = rows[0, :b].astype(bool)
    reset_us = now_us // window_us * window_us + window_us
    return (allowed, rows[1, :b].astype(np.int64),
            np.where(allowed, 0.0, (reset_us - now_us) / 1e6),
            np.full(b, reset_us / 1e6))


# ------------------------------------------------- the step's contract
#
# "Which compiled program decides a batch under this config" is answered
# here and nowhere else. ``step_statics`` is the ONE derivation from a
# Config to the static keyword arguments of ``_sketch_step`` (its twin:
# bucket_kernels.step_statics); every builder of a program around that
# body — build_hashed_step and build_controls below, the
# replicated mesh's (parallel/mesh_kernels.py) and the collective
# router's (ops/route_kernels.py) — takes its statics from it and keys
# its memo on that mapping's items (ops.memoized) plus what the builder
# itself binds, so a field the step reads cannot be missing from a key.
# How a table row is read or written (sort-merge or direct indexing) is
# chosen inside the body from (B, w, platform), in
# ops/sortmerge._use_sortmerge only.
#
# The serving hot path stages ONE uint64 buffer per batch —
# ``[ids(P) | n(P) | now_us(1)]``, one host→device transfer — and the
# step does the rest ON DEVICE: it slices the buffer, narrows ``n``,
# derives (h1, h2) (ops/hashing.split_hash_dev) and ends by packing its
# verdicts into one int32 buffer (pack_window), so a dispatch is one
# transfer in, one program launch and one transfer out, and the host
# never runs per-key hash math after ingest (ADR-011, ADR-010
# addenda). ``premix=True`` additionally applies the splitmix64
# finalizer in-step: the raw-u64-id wire lane (T_ALLOW_HASHED) ships
# tenant ids untouched and the device does ALL the mixing.

_BUILT: Dict[tuple, object] = {}


def step_statics(cfg: Config) -> dict:
    """The static keyword arguments of ``_sketch_step`` for ``cfg``.
    Raises InvalidConfigError for a TOKEN_BUCKET config (sketch_geometry)
    and RuntimeError without 64-bit types (ensure_x64)."""
    from ratelimiter_tpu.core.types import Algorithm

    ensure_x64()
    _, sub_us, SW, S, limit = sketch_geometry(cfg)
    # The side table's slots and its promotion threshold in requests;
    # (0, 0.0) when it is disabled.
    hh = cfg.sketch.hh_slots
    hh_thresh = (max(1.0, float(limit) * cfg.sketch.hh_promote_fraction)
                 if hh else 0.0)
    return dict(limit=limit, sub_us=sub_us, SW=SW, S=S,
                d=cfg.sketch.depth, w=cfg.sketch.width,
                iters=cfg.max_batch_admission_iters,
                weighted=cfg.algorithm is not Algorithm.FIXED_WINDOW,
                conservative=cfg.sketch.conservative_update,
                hh=hh, hh_thresh=hh_thresh, tenants=cfg.hierarchy.tenants)


def build_controls(cfg: Config) -> tuple[Callable, Callable]:
    """Returns (reset, rollover) jitted callables over the (h1, h2)
    operands — rare control-plane dispatches; memoized per static config.
    The host calls ``rollover(state, p)`` whenever the sub-window period
    of the dispatch timestamp differs from the state's period (see
    _rollover for why this is host-driven). Both are replicated
    computations on a mesh's replicated state: the same two programs
    serve every placement."""
    kw = step_statics(cfg)
    reset_kw = {k: kw[k] for k in ("sub_us", "SW", "S", "d", "w",
                                   "weighted", "hh")}
    return memoized(_BUILT, reset_kw, ("controls",), lambda: (
        jax.jit(named("sketch_reset", _sketch_reset, **reset_kw),
                donate_argnums=(0,)),
        jax.jit(named("sketch_rotate", _rollover, SW=kw["SW"], S=kw["S"]),
                donate_argnums=(0,))))


def unstage(staged):
    """``uint64[2P + 1]`` staging buffer -> ``(ids uint64[P], n
    int32[P], now_us int64[])``. The layout is the host's
    (SketchLimiter._acquire_staging); P is static under jit."""
    P = (staged.shape[0] - 1) // 2
    return (staged[:P], staged[P:2 * P].astype(jnp.int32),
            staged[2 * P].astype(jnp.int64))


def unstage_routed(staged):
    """One chip's row ``uint64[2L + 2]`` of the collective launch's
    staging slot -> ``(ids uint64[L], n int32[L], now_us int64[], b
    int64[])``: ``unstage``'s layout followed by the frame's true row
    count, both scalars repeated in every chip's row so that the slot
    shards by rows (CollectiveMeshLimiter._stage_frame)."""
    return (*unstage(staged[:-1]), staged[-1].astype(jnp.int64))


def split_staged(h64, premix: bool, seed: int):
    """(h1, h2) of staged keys: finalized hashes, or raw ids to premix."""
    from ratelimiter_tpu.ops.hashing import split_hash_dev, splitmix64_dev

    with jax.named_scope("hash_split"):
        return split_hash_dev(splitmix64_dev(h64) if premix else h64, seed)


def _sketch_step_staged(state: State, staged, policy=None, hier=None, *,
                        seed: int, premix: bool, **step_kw):
    h64, n, now_us = unstage(staged)
    h1, h2 = split_staged(h64, premix, seed)
    state, (allowed, remaining, _est), run_count = _sketch_step(
        state, h1, h2, n, now_us, policy, hier, counted=True, **step_kw)
    with jax.named_scope("finish"):
        packed = pack_window(allowed, remaining)
        if run_count is None:
            return state, packed
        # The access counts ride home in the one buffer (RUN_TAIL_WORDS):
        # no second fetch.
        return state, jnp.concatenate([packed, jnp.stack(
            [jnp.int32(h1.shape[0]), run_count])])


def build_hashed_step(cfg: Config, *, premix: bool = False) -> Callable:
    """Jitted ``step(state, staged, policy[, hier])`` over one staging
    buffer (see ``unstage``) of finalized 64-bit hashes (premix=False —
    string-key and pre-hashed traffic) or raw u64 ids (premix=True — the
    hashed wire lane); returns ``(state, pack_window's one buffer)``.
    Memoized per static config. Decision-identical to ``_sketch_step``
    over host-split (h1, h2) by the split_hash host/device bit-equality
    (tests/test_hashing_device.py)."""
    kw = step_statics(cfg)
    seed = cfg.sketch.seed
    return memoized(_BUILT, kw, ("step", seed, premix), lambda: jax.jit(
        named("sketch_step", _sketch_step_staged, seed=seed, premix=premix,
              **kw),
        donate_argnums=(0,)))


def _migrate_window(state: State, now_us, *, sub_o: int, SWo: int, So: int,
                    sub_n: int, SWn: int, Sn: int, hh: int):
    """Re-bucket ring state onto a new sub-window geometry (dynamic
    window updates). Every old sub-window's mass is attributed to the
    LAST new period its time span overlaps, so nothing expires earlier
    than it would have under either window — migration can only err
    toward denying, never over-admission. Mass mapped past the new
    window's tail (an old window longer than the new one) drops into the
    boundary-or-older region and ages out exactly like native history.
    """
    p_last = state["last_period"]
    p_now = now_us // sub_n
    sp = state["slab_period"]                              # (So,)
    valid = (sp >= p_last - SWo) & (sp <= p_last - 1)
    q = ((sp + 1) * sub_o - 1) // sub_n                    # last overlapped
    to_cur = valid & (q >= p_now)
    in_ring = valid & (q < p_now) & (q >= p_now - SWn)
    slot = (q % Sn).astype(jnp.int32)

    def rebucket(slabs, cur):
        contrib = slabs * in_ring.reshape((-1,) + (1,) * (slabs.ndim - 1))
        new_slabs = jnp.zeros((Sn,) + slabs.shape[1:],
                              slabs.dtype).at[slot].add(contrib)
        # dtype pinned: jnp.sum would promote int32 to the default int,
        # permanently doubling the hot arrays' width and tripping the
        # next rollover's int64->int32 scatter.
        new_cur = cur + jnp.sum(
            slabs * to_cur.reshape((-1,) + (1,) * (slabs.ndim - 1)),
            axis=0, dtype=cur.dtype)
        return new_slabs, new_cur

    new_slabs, new_cur = rebucket(state["slabs"], state["cur"])
    periods_n = jnp.full((Sn,), _NEVER, jnp.int64).at[slot].max(
        jnp.where(in_ring, q, _NEVER))
    in_window = ((periods_n >= p_now - SWn + 1)
                 & (periods_n <= p_now - 1)).astype(jnp.int32)
    totals_n = (jnp.tensordot(in_window, new_slabs, axes=1)
                .astype(new_cur.dtype) + new_cur)
    out = {"cur": new_cur, "slabs": new_slabs, "totals": totals_n,
           "slab_period": periods_n,
           "last_period": jnp.asarray(p_now, jnp.int64)}
    if "tn_cur" in state:
        # Tenant/global counters re-bucket with the same conservative
        # last-overlapped-period rule as the CMS ring (rebucket() is
        # shape-generic over the trailing axes).
        tn_slabs, tn_cur = rebucket(state["tn_slabs"], state["tn_cur"])
        out.update({
            "tn_cur": tn_cur,
            "tn_slabs": tn_slabs,
            "tn_totals": (jnp.tensordot(in_window, tn_slabs, axes=1)
                          .astype(tn_cur.dtype) + tn_cur),
        })
    if hh:
        hh_slabs, hh_cur = rebucket(state["hh_slabs"], state["hh_cur"])
        hh_totals = (jnp.tensordot(in_window, hh_slabs, axes=1)
                     .astype(hh_cur.dtype) + hh_cur)
        q_hh = ((state["hh_last"] + 1) * sub_o - 1) // sub_n
        out.update({
            "hh_owner": state["hh_owner"],
            "hh_owner2": state["hh_owner2"],
            "hh_cur": hh_cur,
            "hh_slabs": hh_slabs,
            "hh_totals": hh_totals,
            "hh_last": jnp.where(state["hh_last"] == _NEVER,
                                 jnp.int64(_NEVER), q_hh),
        })
    return out


def build_migrate(old_cfg: Config, new_cfg: Config) -> Callable:
    """Jitted ``migrate(state, now_us) -> state`` moving ring state from
    old_cfg's window geometry to new_cfg's. Limit/depth/width/hh must
    match (only the window changes)."""
    ensure_x64()
    _, sub_o, SWo, So, _ = sketch_geometry(old_cfg)
    _, sub_n, SWn, Sn, _ = sketch_geometry(new_cfg)
    if (old_cfg.sketch.depth, old_cfg.sketch.width) != (
            new_cfg.sketch.depth, new_cfg.sketch.width):
        raise InvalidConfigError("window migration cannot change geometry")
    hh = old_cfg.sketch.hh_slots
    # No donation: the ring shapes change (So != Sn in general), so the
    # old buffers cannot be reused anyway and donating only warns.
    return jax.jit(
        named("sketch_migrate", _migrate_window, sub_o=sub_o, SWo=SWo,
              So=So, sub_n=sub_n, SWn=SWn, Sn=Sn, hh=hh))
