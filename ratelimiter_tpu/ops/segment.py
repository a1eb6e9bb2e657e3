"""In-batch same-key sequencing (SURVEY.md §7.4 hard part #1).

Redis serializes decisions; a batched device call does not. A batch holding k
requests for one key must behave like k sequential Lua calls: greedy
conditional consume in batch order (denied requests consume nothing —
the documented contract, ``interface.go:104-105``).

The greedy recurrence ``c_i = c_{i-1} + n_i * [c_{i-1} + n_i <= avail]`` is
not associative, so it cannot be a plain prefix sum. This module computes it
with a bounded fixpoint iteration plus a safety intersection:

1. Stable-sort requests by slot id; segment = run of equal slots.
2. Start from "everyone consumes" and iterate
   ``allowed <- (segment-exclusive-cumsum(n * allowed) + n <= avail)``.
   Each iteration alternates between under- and over-admitting relative to
   the greedy solution and converges monotonically toward it.
3. Safety intersection: one final pass keeps only requests that fit under the
   final mask's own consumption, **intersected with** that mask. Because the
   result is a subset of the mask used to compute consumption, every kept
   request satisfies its quota check a fortiori — the op can under-admit in
   adversarial mixed-n cases but can never over-admit.

Exactness guarantees (tested in tests/test_segment.py):
* uniform n within a segment (incl. the ubiquitous all-n=1 case): exact greedy
  after iteration 1;
* any segment whose greedy solution is reached within ``iters`` fixpoint
  steps: exact.

TPU implementation notes (this shapes everything here):
* no gathers anywhere — permutations are applied by carrying payloads
  through multi-operand stable ``lax.sort`` (gather/scatter cost ~7 ns/elem
  serialized on TPU; sorts and f32 scans are ~ns/elem vectorized);
* the per-segment head value is propagated with a masked cummax instead of
  an index gather: the global exclusive cumsum ``c`` of non-negative
  consumption is non-decreasing, so the max of head-masked ``c`` over the
  prefix IS the segment head's value;
* int32 cumsums go through ops.scans.exact_cumsum_i32 (MXU-blocked limbs);
  f32 uses the fast builtin. Quantities are int64 "micro-units"
  (1 request == 1_000_000 units) in the dense backend and plain f32 request
  counts in the sketch backend; both share this kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ratelimiter_tpu.ops.scans import cumsum_fast, exact_cumsum_i32

MICRO = 1_000_000

#: f32 integers are exact below this; the fast f32 cumsum path is only used
#: while the batch's total consumption stays under it (see admit).
_F32_EXACT = 1 << 24


def _head_prop(c: jnp.ndarray, seg_head: jnp.ndarray) -> jnp.ndarray:
    """Value of ``c`` at each element's segment head. Requires c
    non-decreasing and >= 0 with seg_head[0] True (always true for a
    cumsum of non-negative consumption)."""
    masked = jnp.where(seg_head, c, jnp.zeros_like(c))
    return jax.lax.cummax(masked)


def _segment_exclusive_cumsum(x: jnp.ndarray, seg_head: jnp.ndarray) -> jnp.ndarray:
    """Exclusive cumsum of non-negative x restarting at each segment head."""
    c = cumsum_fast(x) - x  # global exclusive cumsum, non-decreasing
    return c - _head_prop(c, seg_head)


def _segment_exclusive_cumsum_exact_f32(x: jnp.ndarray,
                                        seg_head: jnp.ndarray) -> jnp.ndarray:
    """Exact segment-exclusive cumsum for *integer-valued* f32 x.

    The f32 builtin cumsum loses integer exactness once a partial sum
    crosses 2^24; this path runs the scan on int32 (MXU limb cumsum +
    int32 head propagation — both exact while true prefix sums fit int32)
    and only casts the *segment-relative* value back to f32. The final
    cast is exact below 2^24; above it, the value already exceeds any
    admissible quota (limits are validated < 2^24), so the f32 rounding
    (relative error 2^-24) can never flip a ``cons + n <= avail``
    comparison. Decision-exact for total batch consumption < 2^31.
    """
    xi = x.astype(jnp.int32)
    c = exact_cumsum_i32(xi) - xi
    seg = c - jax.lax.cummax(jnp.where(seg_head, c, jnp.zeros_like(c)))
    return seg.astype(x.dtype)


def admit_sorted(nn: jnp.ndarray, av: jnp.ndarray, seg_head: jnp.ndarray,
                 iters: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The admission itself, on rows ALREADY in slot-sorted order (batch
    order inside a slot): ``(allowed, seen)`` in that same order.
    ``seg_head`` marks each slot's first row. :func:`admit` is the sort,
    this, and the sort back; a caller that has sorted the batch for a
    purpose of its own (the windowed step's run-merged table accesses,
    ops/sketch_kernels.py) calls this and keeps its order."""
    zero = jnp.zeros((), nn.dtype)

    def _solve(excl_cumsum):
        allowed = jnp.ones(nn.shape, dtype=bool)
        for _ in range(iters):
            cons = excl_cumsum(jnp.where(allowed, nn, zero), seg_head)
            allowed = cons + nn <= av
        # Safety intersection: subset of the last mask, checked against that
        # mask's own consumption -> never over-admits (module docstring).
        cons = excl_cumsum(jnp.where(allowed, nn, zero), seg_head)
        allowed = allowed & (cons + nn <= av)
        # Consumption under the final mask, for consistent per-request views.
        cons = excl_cumsum(jnp.where(allowed, nn, zero), seg_head)
        seen = av - cons
        return allowed, seen

    if jnp.issubdtype(nn.dtype, jnp.floating):
        # f32 exactness guard (2^24 precondition): the fast f32 cumsum is
        # only exact while every partial sum of consumption is an exactly
        # representable integer, i.e. total batch consumption < 2^24. The
        # total is data-dependent, so the guard is a runtime cond, not a
        # trace-time assert: mega-batches whose cumulative cost crosses
        # 2^24 take the int32 limb-exact path instead of silently
        # mis-admitting. Floating n_units must be integer-valued request
        # counts (the sketch path's contract).
        total = jnp.sum(nn.astype(jnp.int64))
        return jax.lax.cond(
            total < _F32_EXACT,
            lambda: _solve(_segment_exclusive_cumsum),
            lambda: _solve(_segment_exclusive_cumsum_exact_f32),
        )
    return _solve(_segment_exclusive_cumsum)


def admit(
    sid: jnp.ndarray,        # int32[B] slot/segment id per request
    n_units: jnp.ndarray,    # [B] requested amount (>=0; 0 = padding)
    avail_units: jnp.ndarray,  # [B] per-request available quota (equal within a slot)
    iters: int,
    tails: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Greedy-in-batch-order admission.

    Returns (in original request order):
        allowed:    bool[B]
        seen_units: [B] — free quota as seen by request i (after consumption
                    by allowed same-slot requests earlier in the batch,
                    before its own). ``seen - n*allowed`` is the
                    post-decision remaining; ``n - seen`` is the deficit for
                    retry-after math.
        consumed_units: [B] — n_units where allowed else 0 (original order;
                    callers fold this into state by sid).
        last:       bool[B], only with ``tails`` — the request is the LAST
                    of its slot in the batch. ``seen - consumed`` of that
                    request is what the slot has left after the whole
                    batch, so a caller can write each touched slot ONCE,
                    by a scatter whose indices are unique (the dense
                    backend's write-back). It rides the sort that restores
                    the original order; without ``tails`` the traced
                    program is what it was.
    """
    B = sid.shape[0]
    iota = jax.lax.iota(jnp.int32, B)
    # One stable multi-operand sort replaces argsort + payload gathers.
    s, nn, av, orig = jax.lax.sort((sid, n_units, avail_units, iota),
                                   num_keys=1, is_stable=True)

    seg_head = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), s[1:] != s[:-1]])

    zero = jnp.zeros((), nn.dtype)
    allowed, seen = admit_sorted(nn, av, seg_head, iters)

    # Restore original order with a second sort keyed by the carried index.
    back = (orig, allowed.astype(jnp.int32), seen)
    if tails:
        seg_tail = jnp.concatenate(
            [s[1:] != s[:-1], jnp.ones((1,), dtype=bool)])
        back = back + (seg_tail.astype(jnp.int32),)
    _, allowed_i, seen_o, *tail_i = jax.lax.sort(back, num_keys=1,
                                                 is_stable=True)
    allowed_o = allowed_i.astype(bool)
    consumed_o = jnp.where(allowed_o, n_units, zero)
    if tails:
        return allowed_o, seen_o, consumed_o, tail_i[0].astype(bool)
    return allowed_o, seen_o, consumed_o


def segment_consumption(sid: jnp.ndarray, n_units: jnp.ndarray) -> jnp.ndarray:
    """Segment-exclusive cumsum of (already-masked) consumption, returned
    in ORIGINAL request order: cons[i] = sum of n_units[j] for j < i in
    the same slot. The cascade path (ops/hier_kernels.py) uses this to
    recompute each scope's per-request consumption view under the FINAL
    all-or-nothing mask — a request denied at a later scope must not
    appear consumed in the quantities (seen/remaining, CU targets) the
    earlier scopes report or write. Same sort/cumsum machinery and f32
    exactness guard as :func:`admit`."""
    B = sid.shape[0]
    iota = jax.lax.iota(jnp.int32, B)
    s, nn, orig = jax.lax.sort((sid, n_units, iota), num_keys=1,
                               is_stable=True)
    seg_head = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), s[1:] != s[:-1]])
    if jnp.issubdtype(nn.dtype, jnp.floating):
        total = jnp.sum(nn.astype(jnp.int64))
        cons = jax.lax.cond(
            total < _F32_EXACT,
            lambda: _segment_exclusive_cumsum(nn, seg_head),
            lambda: _segment_exclusive_cumsum_exact_f32(nn, seg_head),
        )
    else:
        cons = _segment_exclusive_cumsum(nn, seg_head)
    _, cons_o = jax.lax.sort((orig, cons), num_keys=1, is_stable=True)
    return cons_o
