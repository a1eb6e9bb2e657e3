"""Sketched token bucket — unbounded-key TOKEN_BUCKET on the CMS backend.

The reference's flagship algorithm (``tokenbucket.go:23-52``) keeps one
{tokens, last_refill} hash per key in Redis; memory grows with key
cardinality (~170 B/user, ``docs/ALGORITHMS.md:635``). This module gives the
same continuous-refill / burst / denial-consumes-nothing semantics at
O(depth x width) memory, independent of key count, via the classic
token-bucket <-> leaky-meter equivalence (GCRA):

    tokens(t) = limit - debt(t),   where debt decays at the refill rate
    (limit/window tokens per second) and clamps at 0; a consume of n adds
    n to debt; allow iff debt + n <= limit.

The meter form sketches cleanly where the token form does not: per-key
*debt* is a non-negative counter, so a count-min sketch over debts keeps
the CMS error direction — a cell holds the SUM of colliding keys' debts,
so the min-over-rows read can only OVERestimate a key's true debt, which
can only cause false *denies*, never over-admission (the same contract as
ops/sketch_kernels.py, SURVEY.md §7.4 hard part #3).

Decay is exact integer math, no float drift (SURVEY.md §7.4 hard part #5):
every cell decays at the SAME rate, so one scalar per-step decay amount
serves the whole (d, w) slab, with a single global remainder carrying
fractional micro-tokens across steps (the per-key analog is
dense_kernels._token_bucket_step's per-slot ``rem``; here the clamp at 0
happens per cell, which is exactly per-key-correct because linear decay
followed by clamp composes: max(0, max(0, x-a)-b) == max(0, x-(a+b))).

Accuracy model (documented tradeoff, measured by evaluation/accuracy.py):
colliding *active* keys share refill — K hot keys in one cell drain it at
K x their admission rate while it refills at 1 x rate, so persistent
colliders are throttled toward one key's worth of combined throughput.
Errors are always toward denying. Width sizing follows the usual CMS rule
(w >> active hot keys); the conservative-update trick does not apply here
(there is no globally-consistent "window read" target — the decayed debt
is a moving quantity), so writes are vanilla sums.

State (see init_state):
    debt int64[d, w]  micro-token debt cells (1 token = 1e6 micro)
    rem  int64[]      global decay remainder, < rate_den
    last int64[]      timestamp of the last step, microseconds
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ratelimiter_tpu.core.clock import MICROS
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.ops import ensure_x64, memoized, named, policy_kernels
from ratelimiter_tpu.ops.dense_kernels import _check_gates
from ratelimiter_tpu.ops.segment import admit
from ratelimiter_tpu.ops.sketch_kernels import (
    _columns,
    join_words,
    pack_rows,
    split_staged,
    split_words,
    unstage,
)
from ratelimiter_tpu.ops.sortmerge import row_gather, row_histogram

State = Dict[str, jnp.ndarray]

#: Cells clamp here on write so debt arithmetic can never overflow int64
#: even under adversarial collision pileups (2^61 micro-tokens = 2.3e12
#: tokens — clamping errs toward denying, preserving the error direction).
_DEBT_CAP = 1 << 61


def init_state(cfg: Config) -> State:
    """All-zero debt == every bucket full (the reference's absent-key
    default, ``tokenbucket.go:31-33``); last=0 makes the first step see a
    huge elapsed whose decay is a no-op on zero debt.

    ``acc`` accumulates LOCAL debt increments since the last DCN export
    (parallel/dcn.py): the step adds its write histogram there too, the
    export snapshots-and-zeroes it, and foreign merges add to ``debt``
    only — so exports can never re-ship foreign traffic (the bucket
    analog of the windowed tier's completed-slab watermark)."""
    d, w = cfg.sketch.depth, cfg.sketch.width
    state = {
        "debt": jnp.zeros((d, w), jnp.int64),
        "acc": jnp.zeros((d, w), jnp.int64),
        "rem": jnp.asarray(0, jnp.int64),
        "last": jnp.asarray(0, jnp.int64),
    }
    T = cfg.hierarchy.tenants
    if T:
        # Hierarchical cascade (ADR-020): tenant/global scopes on the
        # debt-sketch backend are FIXED-WINDOW request counters (index T
        # is the global scope) — a deliberate divergence from the key
        # scope's GCRA meter, keeping tenant math exact int64 at any
        # window length (per-tenant decay rates over a dynamic limit
        # array cannot stay overflow-safe at 365-day windows). tn_period
        # is the window index of the counts; a step in a later window
        # zeroes them lazily.
        state.update({
            "tn_counts": jnp.zeros((T + 1,), jnp.int64),
            "tn_period": jnp.asarray(-(1 << 40), jnp.int64),
        })
    return state


def _decay(state: State, now_us, *, rate_num: int, rate_den: int):
    """Scalar micro-token decay since state['last'], exact and
    overflow-safe. rate = rate_num/rate_den micro-tokens per us, in lowest
    terms (dense_kernels._check_gates guarantees rate_den * rate_num <
    2^62). The quotient arm is clamped so idle-for-years elapsed values
    cannot overflow: past _DEBT_CAP the extra decay is irrelevant because
    every cell has long since clamped at 0."""
    elapsed = jnp.maximum(0, now_us - state["last"])
    e_q = elapsed // rate_den
    acc = (elapsed - e_q * rate_den) * rate_num + state["rem"]
    e_q = jnp.minimum(e_q, _DEBT_CAP // rate_num)
    decay = e_q * rate_num + acc // rate_den
    return decay, acc % rate_den


def _debt_histograms(cols, tokens, w: int):
    """``int64[d, w]`` micro-token debt a batch adds to each cell:
    ``H[r, c] = MICROS * sum(tokens[cols[:, r] == c])`` — what
    ``row_histogram(cols[:, r], tokens.astype(int64) * MICROS, w)`` gives
    for each row, equal to it in wrapping int64 arithmetic for every
    integer ``tokens``, built from 32-bit scatter-adds only. On the TPU a
    scatter-add costs by the index, and an int64 one many times a 32-bit
    one (ADR-004 addendum), so whole tokens are scattered as 32-bit limbs
    and widened over the dense slab: ``tokens = sum(limb_s << s)``, every
    limb but the top one under ``2**bits`` with ``B * (2**bits - 1) <
    2**31``, so no cell's limb sum (nor the sort-merge form's running
    sum) can wrap whatever the batch holds; the top limb keeps the sign.
    The limb count is a trace-time function of the shapes alone: two for
    int32 ``tokens`` up to B = 32,768."""
    B, d = cols.shape
    bits = 31 - (B - 1).bit_length()
    total = jnp.iinfo(tokens.dtype).bits
    hists = None
    for s in range(0, total, bits):
        limb = tokens >> s
        if s + bits < total:
            limb = limb & ((1 << bits) - 1)
        limb = limb.astype(jnp.int32)
        h = jnp.stack([row_histogram(cols[:, r], limb, w)
                       for r in range(d)]).astype(jnp.int64) << s
        hists = h if hists is None else hists + h
    return hists * MICROS


def _bucket_step(state: State, h1, h2, n, now_us, policy=None, hier=None, *,
                 limit: int, rate_num: int, rate_den: int,
                 d: int, w: int, iters: int, tenants: int = 0,
                 window_us: int = 0,
                 axis_name: str | None = None):
    """One batched decision step. Returns (state, (allowed, remaining,
    retry_us)) — the limiter-side retry/reset plumbing is shared with the
    other sketch paths.

    Policy overrides here change a key's burst CAPACITY (cap = limit_k
    micro-tokens); the decay rate stays the global limit/window — debt
    cells are shared by colliding keys, so a per-key decay rate does not
    exist in this representation. Documented divergence from the
    token-form backends (whose overrides scale the refill rate too):
    overridden keys burst to their own limit immediately and refill at
    the default rate. Errors stay toward denying."""
    with jax.named_scope("decay"):
        decay, rem = _decay(state, now_us, rate_num=rate_num,
                            rate_den=rate_den)
    with jax.named_scope("estimate"):
        debt = jnp.maximum(jnp.int64(0), state["debt"] - decay)
        cols = _columns(h1, h2, d, w)                       # (B, d)
        est = None
        for r in range(d):
            (e_r,) = row_gather((debt[r],), cols[:, r])
            est = e_r if est is None else jnp.minimum(est, e_r)

    with jax.named_scope("admit"):
        if policy is not None:
            cap = policy_kernels.limit_for_rows(
                policy, h1, h2, limit, jnp.int64) * MICROS
        else:
            cap = limit * MICROS
        avail = jnp.maximum(jnp.int64(0), cap - est)        # micro-tokens
        n_units = n.astype(jnp.int64) * MICROS
        sid = jax.lax.bitcast_convert_type(h1, jnp.int32)
        allowed, seen, _ = admit(sid, n_units, avail, iters)

    tn_hist = None
    if tenants and hier is not None:
        # Cascade stages 2+3 (ADR-020): fixed-window tenant/global
        # request counters, rolled lazily when the step's timestamp
        # enters a new window. All-or-nothing — the final mask gates the
        # key-scope debt write below, and every scope's consumption view
        # is recomputed under it.
        from ratelimiter_tpu.ops import hier_kernels
        from ratelimiter_tpu.ops.segment import segment_consumption

        tid = hier_kernels.derive_tids(hier, h1, h2, tenants)
        hp = now_us // window_us
        rolled = hp > state["tn_period"]
        counts = jnp.where(rolled, jnp.int64(0), state["tn_counts"])
        avail_sc = hier_kernels.scope_avail(hier["limit"], counts)
        allowed_casc, tn_hist = hier_kernels.cascade_admit(
            allowed, tid, n, avail_sc, hier["weight"], tenants, iters)
        # Final-mask consumption view, cond'd on the cascade having
        # flipped any verdict (same rule as the windowed kernel): no
        # contention → stage-1 seen already reflects the final mask.
        seen = jax.lax.cond(
            jnp.any(allowed_casc != allowed),
            lambda: avail - segment_consumption(
                sid, jnp.where(allowed_casc, n_units, jnp.int64(0))),
            lambda: seen)
        allowed = allowed_casc
        if axis_name is not None:
            tn_hist = jax.lax.psum(tn_hist, axis_name)
        tn_out = {"tn_counts": counts + tn_hist,
                  "tn_period": jnp.maximum(state["tn_period"], hp)}
        # Retry for a request the key scope would admit but the cascade
        # denied: the tenant/global window boundary (when those counters
        # reset), not the refill-deficit formula (whose deficit is <= 0
        # for key-fitting requests).
        cascade_retry = (hp + 1) * window_us - now_us
    elif "tn_counts" in state:
        tn_out = {k: state[k] for k in ("tn_counts", "tn_period")}
        cascade_retry = None
    else:
        tn_out = {}
        cascade_retry = None

    with jax.named_scope("write_back"):
        # Under the FINAL mask (the cascade's, where it ran).
        hists = _debt_histograms(cols, jnp.where(allowed, n, 0), w)
        if axis_name is not None:
            # Multi-chip delta merge: replicated debt, psum of increments
            # over ICI (same invariant as sketch_kernels' delta mode). The
            # psum'd histogram IS the pod's local traffic, so `acc` stays
            # export-correct on meshes too.
            hists = jax.lax.psum(hists, axis_name)
        debt = jnp.minimum(debt + hists, _DEBT_CAP)
        acc = jnp.minimum(state["acc"] + hists, _DEBT_CAP)

    new_state = {"debt": debt,
                 "acc": acc,
                 "rem": rem,
                 "last": jnp.maximum(state["last"], now_us),
                 **tn_out}
    remaining = (seen - jnp.where(allowed, n_units, 0)) // MICROS
    # Reference retry semantics (``tokenbucket.go:122-130``): time to refill
    # the deficit, ceil'd to whole microseconds.
    deficit = jnp.maximum(0, n_units - seen)
    retry_us = jnp.where(allowed, 0, -((-deficit * rate_den) // rate_num))
    if cascade_retry is not None:
        # Cascade-denied rows (deficit 0 at the key scope) retry at the
        # tenant/global window boundary.
        retry_us = jnp.where(~allowed & (deficit <= 0), cascade_retry,
                             retry_us)
    return new_state, (allowed, remaining, retry_us)


def _bucket_reset(state: State, h1, h2, now_us, *,
                  rate_num: int, rate_den: int, d: int, w: int):
    """Per-key reset: zero the key's debt by subtracting its min-estimate
    from all its cells, clamped at 0 (no self-healing sweep exists here, so
    unlike sketch_kernels._sketch_reset transient negatives are not allowed
    to persist). Colliding keys gain allowance — errs toward allowing."""
    decay, rem = _decay(state, now_us, rate_num=rate_num, rate_den=rate_den)
    debt = jnp.maximum(jnp.int64(0), state["debt"] - decay)
    cols = _columns(h1, h2, d, w)
    est = None
    for r in range(d):
        (e_r,) = row_gather((debt[r],), cols[:, r])
        est = e_r if est is None else jnp.minimum(est, e_r)
    hists = jnp.stack([row_histogram(cols[:, r], est, w) for r in range(d)])
    debt = jnp.maximum(jnp.int64(0), debt - hists)
    # Reset is deliberately NOT subtracted from `acc`: the consumed debt
    # it forgives was already exported (or will be) as real local traffic,
    # and a negative export could under-count remotely (over-admission).
    # Cross-pod, a reset key simply recovers locally first.
    out = {"debt": debt, "acc": state["acc"], "rem": rem,
           "last": jnp.maximum(state["last"], now_us)}
    if "tn_counts" in state:
        # Key-scope forgiveness only — tenant/global counters stand
        # (same rule as the windowed sketch's _sketch_reset, ADR-020).
        out.update({k: state[k] for k in ("tn_counts", "tn_period")})
    return out


#: Rows of the debt sketch's packed result: allowed, remaining, and
#: ``retry_us`` as its low and high 32-bit words.
BUCKET_ROWS = 4


def pack_bucket(allowed, remaining, retry_us):
    """The debt sketch's result as it leaves the device: ``int32[4P] =
    [allowed(P) | remaining(P) | retry_us low word(P) | retry_us high
    word(P)]`` — the bucket twin of sketch_kernels.pack_window, with the
    same contract: a plain traced function, the ONE definition, shared
    by the serving step, the replicated mesh's and ops/route_kernels.py.
    ``remaining`` is whole tokens in ``[0, limit_k]`` with ``limit_k *
    1e6 < 2**42`` (dense_kernels._check_gates, SketchTokenBucketLimiter.
    _policy_validate), so under 2**23: one word. ``retry_us`` is the
    step's exact int64 (``tokenbucket.go:122-130``) — a deficit of up to
    ``n`` tokens at the refill rate, or the cascade's window boundary —
    and passes 2**32 us (71 minutes) on long windows, so it is split by
    a mask and a shift, no 64-bit value and no float among the outputs."""
    return pack_rows(allowed, remaining, *split_words(retry_us))


def unpack_bucket(rows: np.ndarray, b: int, now_us: int, window_us: int):
    """BatchResult's four columns from pack_bucket's rows, on the host
    (see sketch_kernels.unpack_window): retry-after = the step's
    ``retry_us`` / 1e6 in IEEE float64; reset_at is the reference's now +
    window approximation (``tokenbucket.go:159-165``)."""
    return (rows[0, :b].astype(bool), rows[1, :b].astype(np.int64),
            join_words(rows[2, :b], rows[3, :b]).astype(np.float64) / 1e6,
            np.full(b, (now_us + window_us) / 1e6))


_BUILT: Dict[tuple, object] = {}


def step_statics(cfg: Config) -> dict:
    """The static keyword arguments of ``_bucket_step`` for ``cfg`` —
    the ONE derivation (see sketch_kernels.step_statics, whose contract
    this twins): the refill rate in lowest terms, the geometry, and the
    cascade's tenant count and fixed window. Raises RuntimeError
    without 64-bit types."""
    ensure_x64()
    W, num, den = _check_gates(cfg)
    return dict(limit=cfg.limit, rate_num=num, rate_den=den,
                d=cfg.sketch.depth, w=cfg.sketch.width,
                iters=cfg.max_batch_admission_iters,
                tenants=cfg.hierarchy.tenants, window_us=W)


def build_controls(cfg: Config) -> Tuple[Callable]:
    """Returns (reset,), jitted over the (h1, h2) operands and memoized
    per static config; no rollover, decay is inside the step. A
    replicated computation on a mesh's replicated state: the same
    program serves every placement."""
    kw = step_statics(cfg)
    reset_kw = {k: kw[k] for k in ("rate_num", "rate_den", "d", "w")}
    return memoized(_BUILT, reset_kw, ("controls",), lambda: (
        jax.jit(named("bucket_reset", _bucket_reset, **reset_kw),
                donate_argnums=(0,)),))


def _bucket_step_staged(state: State, staged, policy=None, hier=None, *,
                        seed: int, premix: bool, **step_kw):
    h64, n, now_us = unstage(staged)
    h1, h2 = split_staged(h64, premix, seed)
    state, (allowed, remaining, retry_us) = _bucket_step(
        state, h1, h2, n, now_us, policy, hier, **step_kw)
    with jax.named_scope("finish"):
        return state, pack_bucket(allowed, remaining, retry_us)


def build_hashed_step(cfg: Config, *, premix: bool = False) -> Callable:
    """Jitted ``step(state, staged, policy[, hier])`` over one staging
    buffer, returning ``(state, pack_bucket's one buffer)`` — the
    bucket twin of sketch_kernels.build_hashed_step (ADR-011, ADR-010
    addendum)."""
    kw = step_statics(cfg)
    seed = cfg.sketch.seed
    return memoized(_BUILT, kw, ("step", seed, premix), lambda: jax.jit(
        named("bucket_step", _bucket_step_staged, seed=seed, premix=premix,
              **kw),
        donate_argnums=(0,)))
