"""Fused batched decision kernels over dense slot-addressed state.

These are the TPU-native replacements for the reference's three Lua scripts
(SURVEY.md §2.2): where Redis executes one interpreted script per request
under a global lock, each kernel here decides a whole batch in one jitted
XLA call — resolve each row's key to its slot in the device-resident
directory (ops/directory.py: lookup and insertion inside this same
program), gather state for the batch's slots, sequence same-slot requests
with ops.segment.admit, scatter the touched rows back IN PLACE into the
donated columns. Nothing a dispatch does scales with the capacity: the
work is the batch's — true on the chip since PR 43, which is why every
capacity-sized leaf below is 32-bit. State lives in HBM across calls
(donated buffers); time is an explicit int64-microsecond operand
(SURVEY.md §2.4.14).

The integer recurrences are bit-identical to algorithms/exact.py (see its
module docstring for the micro-token / window-scaled representations), with
an int64-overflow gate checked at build time: configs too large for the
exact-integer path (limits or windows beyond the gates below) raise at
construction rather than silently losing precision.

State layout: ``cols:uint32[2K, C+1]``, the rule's K int64 columns
(``COLUMNS``) as their 32-bit words — the low words stacked in rows
``0..K-1``, the high words in rows ``K..2K-1`` — one slot a column
index; the last slot is the padding slot that padding rows and rows the
directory could not place are sent to — they carry n=0 and are discarded
on the host. Stacked, a batch's rows are read by ONE gather and written
by ONE scatter: on the TPU a gather or scatter costs by the index, not
by the element (PERF.md §6, PR 33: three scatters 1.15 ms, one 0.1).
The 64-bit values exist for the batch's rows only: gathered words are
joined to ``int64[B]`` (``_read``), run through the rule unchanged, and
split again where they are scattered (``_write_once``). The TPU has no
64-bit vectors, so a 64-bit array is split where it enters a program
and recombined where it leaves: held as int64 the state cost two passes
over the whole table a dispatch (PERF.md §6, PR 42: 30.7 of a 32.2 ms
step at 2^26 entries). No leaf sized by the capacity is 64-bit, at any
program's boundary (tests/test_contract_dense.py pins it); the snapshot
FILE keeps int64 arrays, converted on the host (algorithms/dense.py).

* fixed window:  count, win_start (us)
* sliding:       curr, prev, win_start (us)
* token bucket:  tokens (micro-tokens), rem (refill remainder), last (us)
* the directory: dir_lo, dir_hi: uint32[NB, W], NB * W == C, a key's two
                 words (ops/directory.py); entry (b, l) is slot b * W +
                 l. An entry that holds no key always has a pristine
                 state column, so inserting a key writes the key and
                 nothing else.

The serving step (``build_hashed_step``, module ``jit_dense_step``) takes
ONE staged uint64 buffer ``[ids | n | now_us]`` (sketch_kernels.unstage)
and the device-resident override table, and returns the new state and ONE
int32 buffer (``pack_dense``): the four result columns and four counts of
the directory. ``build_step`` is the slot-addressed core alone (the scan
benchmark's and the tests' shape).

Per-key policy overrides (ratelimiter_tpu/policy/): the table rides every
dispatch, and a vectorized binary search (ops/policy_kernels.lookup_i64)
resolves each request's effective (limit, window, refill rate) INSIDE the
fused step — under a ``lax.cond`` on the table's first key, so a
deployment with no override pays one scalar read (the branch
policy_kernels.limit_for_rows takes for the sketches). With
``policy=None`` the parameters are the config's, baked static. Because
windows become per-request, retry/reset leave the host: each rule
returns (new_state, (allowed, remaining, retry_us, reset_us)) with
reset_us the absolute reset/refill timestamp.

Exact integer state math needs real int64 (microsecond timestamps and
micro-token levels exceed int32): every factory calls ops.ensure_x64()
and refuses to build without jax_enable_x64 — the flag is the embedding
process's to set, never flipped at import time (a test pins that).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ratelimiter_tpu.core.clock import MICROS, to_micros
from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.core.errors import InvalidConfigError
from ratelimiter_tpu.core.types import Algorithm
from ratelimiter_tpu.ops import (
    directory,
    ensure_x64,
    memoized,
    named,
    policy_kernels,
)
from ratelimiter_tpu.ops.segment import admit

State = Dict[str, jnp.ndarray]
#: The rows of ``cols``, per rule, in order.
COLUMNS = {Algorithm.FIXED_WINDOW: ("count", "win_start"),
           Algorithm.SLIDING_WINDOW: ("curr", "prev", "win_start"),
           Algorithm.TPU_SKETCH: ("curr", "prev", "win_start"),
           Algorithm.TOKEN_BUCKET: ("tokens", "rem", "last")}
#: allowed, remaining, retry_us, reset_us (per request)
Outputs = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]


def _resolve(policy, keyq, names, defaults):
    """Per-request effective parameters and the predicate they hang on:
    ``(defaults, None)`` — python ints, baked static — when no policy
    table rides the dispatch, else ``(int64[B] columns, overridden)``
    from the binary-search lookup over the device-resident table, under
    the branch policy_kernels.limit_for_rows takes: the table is sorted
    with its PAD_KEY rows last, so a first key of PAD_KEY
    (``overridden`` false) means no row can change an answer and the
    13-gather descent is skipped. Both arms give the same values in every
    case."""
    if policy is None:
        return defaults, None

    def lookup():
        idx, found = policy_kernels.lookup_i64(policy["key"], keyq)
        return tuple(
            jnp.where(found, policy[name][idx], jnp.int64(default))
            for name, default in zip(names, defaults))

    def no_entries():
        return tuple(jnp.full(keyq.shape, default, jnp.int64)
                     for default in defaults)

    overridden = policy["key"][0] != policy_kernels.PAD_KEY
    return jax.lax.cond(overridden, lookup, no_entries), overridden


def _divmod_rows(x, d):
    """Floor ``(x // d, x % d)`` of int64 rows by int64 rows ``d > 0``,
    as a 64-pass restoring division in a loop. XLA's own vector int64
    division is the same passes unrolled, and on the TPU each such op
    costs ~9 s of compile (PERF.md §6, PR 33): three of them a program
    and eleven pad shapes made the dense step a quarter of an hour to
    compile cold. Exact for every int64 ``x`` but the minimum."""
    neg = x < 0
    n = jnp.where(neg, -x, x).astype(jnp.uint64)
    du = d.astype(jnp.uint64)
    one = jnp.uint64(1)

    def body(i, c):
        q, r = c
        bit = (63 - i).astype(jnp.uint64)
        r = (r << one) | ((n >> bit) & one)
        ge = r >= du
        return q | (ge.astype(jnp.uint64) << bit), jnp.where(ge, r - du, r)

    zero = jnp.zeros(n.shape, jnp.uint64)
    q, r = jax.lax.fori_loop(0, 64, body, (zero, zero))
    q, r = q.astype(jnp.int64), r.astype(jnp.int64)
    odd = neg & (r != 0)
    return (jnp.where(neg, -q - odd.astype(jnp.int64), q),
            jnp.where(odd, d - r, r))


def _divmod(x, d, static: int, overridden):
    """Floor ``(x // d, x % d)`` by a rule parameter: ``d`` is the python
    int ``static`` itself without a policy table (``overridden`` None),
    else per-row values that all equal ``static`` unless ``overridden``.
    So the division by the constant — which the compiler turns into
    multiplies — is what runs on a deployment with no override, and the
    by-rows loop only where an entry can change a divisor."""
    if overridden is None:
        return x // d, x % d
    x = jnp.broadcast_to(x, d.shape)
    return jax.lax.cond(overridden, lambda: _divmod_rows(x, d),
                        lambda: (x // static, x % static))


def _read(state: State, sid):
    """The batch's rows of every column, ``int64[K, B]``: ONE gather of
    the words at the batch's slots, joined."""
    rows = state["cols"][:, sid]
    k = rows.shape[0] // 2
    return directory.join(rows[:k], rows[k:])


def _write_once(state: State, sid, last, *columns) -> State:
    """The state with ``columns`` (one int64[B] a column of ``COLUMNS``,
    in order) written at the batch's slots as their words, each touched
    slot ONCE and all words by one scatter: a request that is the last of
    its slot in the batch (``admit(..., tails=True)``) keeps its slot,
    every other one gets an index of its own past the end, which
    ``mode="drop"`` discards. No two indices are equal, so the scatter is
    told so."""
    at = jnp.where(last, sid, _PAST + jax.lax.iota(jnp.int32, sid.shape[0]))
    rows = jnp.stack([jnp.broadcast_to(c, sid.shape) for c in columns])
    return {**state, "cols": state["cols"].at[:, at].set(
        jnp.concatenate(directory.words(rows)), mode="drop",
        unique_indices=True)}


#: Where dropped scatter indices start: past any column (step_statics
#: refuses a capacity that reaches it).
_PAST = 1 << 30


def _bcast(x, like):
    """Broadcast a (possibly scalar) time quantity to per-request shape."""
    return jnp.broadcast_to(jnp.asarray(x, jnp.int64), like.shape)


def check_gate_values(limit: int, window_us: int) -> tuple[int, int]:
    """Overflow gates for the exact-integer paths, for one (limit,
    window_us) operating point — the base config AND every policy-table
    override entry must pass (policy/table.py re-runs this per entry, so
    an override a kernel cannot decide exactly is refused at set time).
    Returns the reduced refill fraction (rate_num, rate_den)."""
    W = window_us
    g = math.gcd(limit * MICROS, W)
    num, den = limit * MICROS // g, W // g
    # token bucket: elapsed*num + rem with elapsed < W, rem < den
    if W * num >= 2**62:
        raise InvalidConfigError(
            "limit*window too large for exact integer token math "
            f"(window_us*rate_num = {W * num} >= 2^62)")
    # sliding window: counts*(W) terms and the micro-rescale (x % W) * MICROS
    if limit * W >= 2**61 or W * MICROS >= 2**63:
        raise InvalidConfigError(
            "limit*window too large for exact integer sliding-window math "
            f"(limit*window_us = {limit * W} >= 2^61)")
    # admission cumsum: batch_total <= B * limit * MICROS; B <= 2^20 assumed
    if limit * MICROS >= 2**42:
        raise InvalidConfigError(
            f"limit {limit} too large for micro-unit batch accounting (>= 2^42/1e6)")
    return num, den


def _check_gates(cfg: Config) -> tuple[int, int, int]:
    """Config-level gate wrapper. Returns (window_us, rate_num, rate_den)."""
    W = to_micros(cfg.window)
    num, den = check_gate_values(cfg.limit, W)
    return W, num, den


def _scale_to_micro(x_winscale: jnp.ndarray, W, window_us: int,
                    overridden) -> jnp.ndarray:
    """floor(x * MICROS / W) without int64 overflow, for
    x <= limit*W < 2^61. Exactness of comparisons is preserved:
    n*MICROS <= floor(x*MICROS/W)  <=>  n*W <= x  for integer n."""
    q, r = _divmod(x_winscale, W, window_us, overridden)
    return q * MICROS + _divmod(r * MICROS, W, window_us, overridden)[0]


# --------------------------------------------------------------- fixed window

def _fixed_window_step(state: State, sid, n, now_us, policy=None, keyq=None,
                       *, limit, window_us, iters):
    (lim, W), over = _resolve(policy, keyq, ("limit", "window_us"),
                              (limit, window_us))
    # per-request grid when windows are per-key
    cur_ws = _divmod(now_us, W, window_us, over)[0] * W
    count, win_start = _read(state, sid)
    stale = win_start != cur_ws
    count_eff = jnp.where(stale, 0, count)

    n_units = n * MICROS
    avail_units = (lim - count_eff) * MICROS
    with jax.named_scope("admit"):
        allowed, seen, consumed, last = admit(sid, n_units, avail_units,
                                              iters, tails=True)

    with jax.named_scope("write_back"):
        # Touched rows only, in place, each slot once: its count with
        # stale windows rolled to 0, plus what the batch consumed of it
        # (whole requests; ``seen - consumed`` of a slot's last request
        # is what the slot has left).
        new_state = _write_once(
            state, sid, last,
            count_eff + (avail_units - (seen - consumed)) // MICROS, cur_ws)
    remaining = (seen - jnp.where(allowed, n_units, 0)) // MICROS
    reset_us = _bcast(cur_ws + W, remaining)
    retry_us = jnp.where(allowed, 0, reset_us - now_us)
    return new_state, (allowed, remaining, retry_us, reset_us)


# ------------------------------------------------------------- sliding window

def _sliding_window_step(state: State, sid, n, now_us, policy=None, keyq=None,
                         *, limit, window_us, iters):
    (lim, W), over = _resolve(policy, keyq, ("limit", "window_us"),
                              (limit, window_us))
    cur_ws = _divmod(now_us, W, window_us, over)[0] * W
    curr, prev, ws = _read(state, sid)
    current = ws == cur_ws
    rolled_one = ws == cur_ws - W
    curr_eff = jnp.where(current, curr, 0)
    prev_eff = jnp.where(current, prev, jnp.where(rolled_one, curr, 0))

    elapsed = now_us - cur_ws
    free_scaled = lim * W - prev_eff * (W - elapsed) - curr_eff * W
    avail_units = _scale_to_micro(free_scaled, W, window_us, over)
    n_units = n * MICROS
    with jax.named_scope("admit"):
        allowed, seen, consumed, last = admit(sid, n_units, avail_units,
                                              iters, tails=True)

    with jax.named_scope("write_back"):
        new_state = _write_once(
            state, sid, last,
            curr_eff + (avail_units - (seen - consumed)) // MICROS,
            prev_eff, cur_ws)
    remaining = (seen - jnp.where(allowed, n_units, 0)) // MICROS
    reset_us = _bcast(cur_ws + W, remaining)
    retry_us = jnp.where(allowed, 0, reset_us - now_us)
    return new_state, (allowed, remaining, retry_us, reset_us)


# --------------------------------------------------------------- token bucket

def _token_bucket_step(state: State, sid, n, now_us, policy=None, keyq=None,
                       *, limit, window_us, rate_num, rate_den, iters):
    (lim, W, num, den), over = _resolve(
        policy, keyq, ("limit", "window_us", "rate_num", "rate_den"),
        (limit, window_us, rate_num, rate_den))
    cap = lim * MICROS
    with jax.named_scope("refill"):
        tokens, rem, last = _read(state, sid)

        elapsed = jnp.maximum(0, now_us - last)
        full = elapsed >= W  # time-to-full from any level <= window
        acc = jnp.where(full, 0, elapsed) * num + rem
        refill, rem_r = _divmod(acc, den, rate_den, over)
        tokens_r = tokens + refill
        capped = full | (tokens_r >= cap)
        tokens_eff = jnp.where(capped, cap, tokens_r)
        rem_eff = jnp.where(capped, 0, rem_r)

    n_units = n * MICROS
    with jax.named_scope("admit"):
        allowed, seen, consumed, tail = admit(sid, n_units, tokens_eff,
                                              iters, tails=True)

    with jax.named_scope("write_back"):
        # Touched rows only, in place, each slot once: the refilled level
        # less what the batch consumed of it, which is what the slot's
        # last request saw less what it took itself.
        new_state = _write_once(state, sid, tail, seen - consumed,
                                rem_eff, now_us)
    remaining = (seen - jnp.where(allowed, n_units, 0)) // MICROS
    # Reference ``tokenbucket.go:122-130``: deficit/rate, ceil'd (exact.py).
    deficit = jnp.maximum(0, n_units - seen)
    retry_us = jnp.where(
        allowed, 0, -_divmod(-deficit * den, num, rate_num, over)[0])
    # Reference reset_at approximation: now + time to fill the whole bucket
    # from empty (``tokenbucket.go:161-165``) == now + window.
    reset_us = _bcast(now_us + W, remaining)
    return new_state, (allowed, remaining, retry_us, reset_us)


# ------------------------------------------------------------------- factory

def fresh_row(algorithm: Algorithm, limit: int) -> Tuple[int, ...]:
    """The pristine value of each column of ``COLUMNS[algorithm]``: what
    ``init_state`` fills with, what an entry that holds no key has, and
    what a reclaimed one goes back to. Window counters are zero; token
    buckets are full with last=0: the first touch sees elapsed >= window
    and saturates at capacity, which is exactly the reference's
    or-capacity default for absent keys (``tokenbucket.go:31-33``) — and
    with a policy override, the step's per-request cap clamp makes the
    first touch saturate at the KEY'S capacity, so fresh overridden keys
    burst to their own limit."""
    if algorithm is Algorithm.TOKEN_BUCKET:
        return (limit * MICROS, 0, 0)
    return (0,) * len(COLUMNS[algorithm])


def split_host(x):
    """Host twin of ``directory.words``, stacked: ``int64[K, ...]`` ->
    ``uint32[2K, ...]``, low words first (the layout of ``cols``)."""
    import numpy as np

    x = np.asarray(x, np.int64)
    return np.concatenate([(x & 0xFFFFFFFF).astype(np.uint32),
                           (x >> 32).astype(np.uint32)])


def init_state(algorithm: Algorithm, capacity: int, limit: int) -> State:
    """Fresh ``cols`` of capacity+1 slots (last = padding slot), every
    slot ``fresh_row``'s words."""
    ensure_x64()
    fresh = jnp.asarray(split_host(fresh_row(algorithm, limit)))
    return {"cols": jnp.tile(fresh[:, None], (1, capacity + 1))}


#: Compiled steps memoized by their static parameters: limiter instances with
#: the same (algorithm, limit, window, iters) share one jitted callable, so
#: JAX's trace cache is hit instead of recompiling per instance.
_STEP_CACHE: Dict[tuple, Callable] = {}


def _step_fn(cfg: Config) -> Callable:
    """The (un-jitted) step function for cfg's algorithm, statics bound."""
    W, num, den = _check_gates(cfg)
    common = dict(limit=cfg.limit, window_us=W, iters=cfg.max_batch_admission_iters)
    if cfg.algorithm is Algorithm.FIXED_WINDOW:
        return partial(_fixed_window_step, **common)
    if cfg.algorithm in (Algorithm.SLIDING_WINDOW, Algorithm.TPU_SKETCH):
        return partial(_sliding_window_step, **common)
    if cfg.algorithm is Algorithm.TOKEN_BUCKET:
        return partial(_token_bucket_step, **common, rate_num=num, rate_den=den)
    raise InvalidConfigError(f"unsupported algorithm {cfg.algorithm}")


def build_step(cfg: Config) -> Callable[[State, jnp.ndarray, jnp.ndarray, jnp.ndarray],
                                        Tuple[State, Outputs]]:
    """Returns the jitted batched step for cfg's algorithm. State buffers are
    donated: the caller must treat the passed-in state as consumed. Call as
    ``step(state, sid, n, now_us[, policy, keyq])`` — the optional trailing
    operands carry the device-resident override table and the batch's int64
    search keys (ops/policy_kernels.py)."""
    ensure_x64()
    W, _, _ = _check_gates(cfg)
    cache_key = (cfg.algorithm, cfg.limit, W, cfg.max_batch_admission_iters)
    cached = _STEP_CACHE.get(cache_key)
    if cached is not None:
        return cached
    step = jax.jit(_step_fn(cfg), donate_argnums=(0,))
    _STEP_CACHE[cache_key] = step
    return step


# ------------------------------------------------- the served step

#: Rows of the dense step's packed result: allowed, remaining, ``retry_us``
#: as its two words, ``reset_us - now_us`` as its two words.
DENSE_ROWS = 6

#: The column whose value says when an entry was last touched (what the
#: reclaim pass compares with its horizon), per rule.
_STAMP = {Algorithm.FIXED_WINDOW: "win_start",
          Algorithm.SLIDING_WINDOW: "win_start",
          Algorithm.TPU_SKETCH: "win_start",
          Algorithm.TOKEN_BUCKET: "last"}


def column(algorithm: Algorithm, name: str) -> int:
    """The row of ``cols`` that holds ``name``."""
    return COLUMNS[algorithm].index(name)


def init_directory_state(cfg: Config, capacity: int) -> State:
    """``init_state`` plus an empty directory of ``capacity`` entries."""
    geo = directory.geometry(capacity, cfg.dense.lanes, cfg.dense.probe_bound)
    dir_lo, dir_hi = directory.init_keys(geo["nb"], geo["w"])
    return {**init_state(cfg.algorithm, capacity, cfg.limit),
            "dir_lo": dir_lo, "dir_hi": dir_hi}


def step_statics(cfg: Config, capacity: int) -> dict:
    """The static keyword arguments of ``_dense_step_staged`` for ``cfg``
    on a table of ``capacity`` entries — the ONE derivation (see
    sketch_kernels.step_statics): the rule's parameters and the
    directory's geometry. Every builder's memo key is computed from it."""
    ensure_x64()
    if not 0 < capacity < _PAST:
        raise InvalidConfigError(
            f"dense capacity must be in [1, 2**30), got {capacity}")
    W, num, den = _check_gates(cfg)
    kw = dict(algorithm=cfg.algorithm, limit=cfg.limit, window_us=W,
              iters=cfg.max_batch_admission_iters,
              **directory.geometry(capacity, cfg.dense.lanes,
                                   cfg.dense.probe_bound))
    if cfg.algorithm is Algorithm.TOKEN_BUCKET:
        kw.update(rate_num=num, rate_den=den)
    return kw


def _rule(algorithm: Algorithm):
    if algorithm is Algorithm.FIXED_WINDOW:
        return _fixed_window_step
    if algorithm in (Algorithm.SLIDING_WINDOW, Algorithm.TPU_SKETCH):
        return _sliding_window_step
    if algorithm is Algorithm.TOKEN_BUCKET:
        return _token_bucket_step
    raise InvalidConfigError(f"unsupported algorithm {algorithm}")


def pack_dense(allowed, remaining, retry_us, reset_in_us, tail):
    """The dense step's result as it leaves the device: ``int32[6P + 4] =
    [allowed | remaining | retry_us low, high | (reset_us - now_us) low,
    high | lookups, probes, inserts, unplaced]`` — sketch_kernels.
    pack_rows' format with the directory's counts as its tail words
    (sketch_kernels.result_rows reads both). ``remaining`` is whole
    requests in ``[0, limit_k]``, under 2**22 by the gates; the two
    durations are exact int64 and pass 2**32 us on long windows."""
    from ratelimiter_tpu.ops.sketch_kernels import pack_rows, split_words

    return jnp.concatenate([
        pack_rows(allowed, remaining, *split_words(retry_us),
                  *split_words(reset_in_us)),
        jnp.stack(tail).astype(jnp.int32)])


def unpack_dense(rows, b: int, now_us: int, window_us: int):
    """BatchResult's four columns from pack_dense's rows, on the host:
    the step's exact integer microseconds over 1e6 in IEEE float64, as
    algorithms/exact.py computes them."""
    import numpy as np

    from ratelimiter_tpu.ops.sketch_kernels import join_words

    return (rows[0, :b].astype(bool), rows[1, :b].astype(np.int64),
            join_words(rows[2, :b], rows[3, :b]).astype(np.float64) / MICROS,
            (now_us + join_words(rows[4, :b], rows[5, :b])) / MICROS)


def _dense_step_staged(state: State, staged, policy, *, premix: bool,
                       algorithm, nb: int, w: int, pb: int, **rule_kw):
    """One dispatch: directory, rule, packed result. ``staged`` is
    sketch_kernels.unstage's buffer of finalized 64-bit key hashes, or —
    ``premix`` — of raw ids the step finalizes with splitmix64 itself."""
    from ratelimiter_tpu.ops.hashing import splitmix64_dev
    from ratelimiter_tpu.ops.sketch_kernels import unstage

    ids, n, now_us = unstage(staged)
    cap = nb * w
    if premix:
        with jax.named_scope("hash_split"):
            ids = splitmix64_dev(ids)
    keyq = ids.astype(jnp.int64)            # the override table's key
    valid = n > 0                           # padding rows carry n = 0
    (dir_lo, dir_hi), slot, placed, claimed, probes = directory.probe(
        (state["dir_lo"], state["dir_hi"]), directory.canon(ids), valid,
        nb=nb, w=w, pb=pb, insert=True)
    # A row without an entry goes to the padding slot with n = 0: it
    # reads and writes nothing of any key.
    sid = jnp.where(placed, slot, cap)
    state, (allowed, remaining, retry_us, reset_us) = _rule(algorithm)(
        state, sid, jnp.where(placed, n, 0).astype(jnp.int64), now_us,
        policy, keyq, **rule_kw)
    with jax.named_scope("finish"):
        unplaced = valid & ~placed
        tail = (jnp.sum(valid, dtype=jnp.int32), probes,
                directory.distinct(slot, claimed),
                jnp.sum(unplaced, dtype=jnp.int32))
        zero = jnp.int64(0)
        return {**state, "dir_lo": dir_lo, "dir_hi": dir_hi}, pack_dense(
            allowed | unplaced,
            jnp.where(unplaced, zero, jnp.maximum(remaining, zero)),
            jnp.where(unplaced, zero, retry_us), reset_us - now_us, tail)


_BUILT: Dict[tuple, object] = {}


def build_hashed_step(cfg: Config, capacity: int, *,
                      premix: bool = False) -> Callable:
    """Jitted ``step(state, staged, policy)`` -> ``(state, pack_dense's
    one buffer)``, module ``jit_dense_step``; state is donated."""
    kw = step_statics(cfg, capacity)
    return memoized(_BUILT, kw, ("step", premix), lambda: jax.jit(
        named("dense_step", _dense_step_staged, premix=premix, **kw),
        donate_argnums=(0,)))


def _dense_reclaim(state: State, now_us, fresh, *, stamp: int, nb: int,
                   w: int, pb: int, horizon_us: int):
    """The table-sized pass (``jit_dense_reclaim``): entries idle for the
    horizon (by column ``stamp`` of ``COLUMNS``) are given up and their slots
    made pristine (``fresh int64[K]``), tombstones no live key walked
    past become EMPTY. Returns ``(state, entries freed)``."""
    cap = nb * w
    with jax.named_scope("reclaim"):
        cols = state["cols"]
        k = cols.shape[0] // 2
        stamps = directory.join(cols[stamp, :cap], cols[k + stamp, :cap])
        (dir_lo, dir_hi), freed = directory.reclaim(
            (state["dir_lo"], state["dir_hi"]), stamps.reshape(nb, w),
            now_us, nb=nb, w=w, pb=pb, horizon_us=horizon_us)
        flat = jnp.concatenate([freed.reshape(cap), jnp.zeros((1,), bool)])
        return ({"cols": jnp.where(flat[None, :], _fresh_words(fresh), cols),
                 "dir_lo": dir_lo, "dir_hi": dir_hi},
                jnp.sum(freed, dtype=jnp.int32))


def _fresh_words(fresh):
    """``fresh int64[K]`` (the pristine row, a program's operand) as the
    ``uint32[2K, 1]`` column of words it is in ``cols``."""
    return jnp.concatenate(directory.words(fresh))[:, None]


def _dense_forget(state: State, ids, valid, fresh, *, clear, nb: int,
                  w: int, pb: int):
    """Find each key (no insertion). ``clear`` None (reset): its entry
    becomes a tombstone and its slot pristine (``fresh int64[K]``);
    ``clear`` a column of ``COLUMNS``: both its words of the slot are
    zeroed (the token bucket's refill remainder, when an override changed
    the rate it is denominated in). Returns ``(state, keys found)``."""
    cap = nb * w
    cols, dir_lo, dir_hi = state["cols"], state["dir_lo"], state["dir_hi"]
    _, slot, found, _, _ = directory.probe(
        (dir_lo, dir_hi), directory.canon(ids), valid,
        nb=nb, w=w, pb=pb, insert=False)
    at = jnp.where(found, slot, cap + 1)           # out of range: dropped
    if clear is not None:
        both = jnp.asarray([clear, cols.shape[0] // 2 + clear])
        cols = cols.at[both[:, None], at[None, :]].set(0, mode="drop")
    else:
        entry = (jnp.where(found, slot // w, nb), slot % w)
        dir_lo = dir_lo.at[entry].set(jnp.uint32(directory.TOMB), mode="drop")
        dir_hi = dir_hi.at[entry].set(jnp.uint32(0), mode="drop")
        cols = cols.at[:, at].set(
            jnp.broadcast_to(_fresh_words(fresh),
                             (cols.shape[0], at.shape[0])), mode="drop")
    return ({"cols": cols, "dir_lo": dir_lo, "dir_hi": dir_hi},
            jnp.sum(found, dtype=jnp.int32))


def build_controls(cfg: Config, capacity: int) -> Tuple[Callable, ...]:
    """``(reclaim, forget, clear_rem)``, jitted and memoized per static
    config: ``reclaim(state, now_us, fresh)``, ``forget(state, ids,
    valid, fresh)`` and ``clear_rem(state, ids, valid, fresh)``, each
    returning ``(state, count)`` with the state donated. Control-plane
    programs: the decision step never runs them."""
    kw = step_statics(cfg, capacity)
    geo = {k: kw[k] for k in ("nb", "w", "pb")}
    reclaim_kw = dict(geo, stamp=column(cfg.algorithm, _STAMP[cfg.algorithm]),
                      horizon_us=2 * kw["window_us"])
    rem = (column(cfg.algorithm, "rem")
           if cfg.algorithm is Algorithm.TOKEN_BUCKET else 0)
    return (
        memoized(_BUILT, reclaim_kw, ("reclaim",), lambda: jax.jit(
            named("dense_reclaim", _dense_reclaim, **reclaim_kw),
            donate_argnums=(0,))),
        memoized(_BUILT, geo, ("forget",), lambda: jax.jit(
            named("dense_forget", _dense_forget, clear=None, **geo),
            donate_argnums=(0,))),
        memoized(_BUILT, geo, ("clear", rem), lambda: jax.jit(
            named("dense_clear_rem", _dense_forget, clear=rem, **geo),
            donate_argnums=(0,))),
    )


# ------------------------------------- table-sized control updates
#
# What a dynamic limit or window does to EVERY slot: elementwise selects
# over the columns with host scalars as operands. Each ``update(was,
# *scalars)`` takes the columns by name as int64[C+1] and returns the
# ones it replaces; ``_dense_rewrite`` runs it between a join and a
# split inside one program, so the 64-bit columns never cross a
# boundary. Control plane: the decision step never runs them.

def shift_tokens(was, delta, cap):
    """Dynamic limit, token bucket: levels move by the limit's delta,
    clamped to [0, new cap]; the remainder resets."""
    return dict(tokens=jnp.clip(was["tokens"] + delta, 0, cap), rem=0)


def clear_rem(was):
    """Dynamic window, token bucket: the rate changed, the remainder is
    in the old one's denomination (< 1 micro-token, toward denying)."""
    return dict(rem=0)


def rebucket_fixed(was, cur_old, new_start):
    """Dynamic window, fixed window: the live old window's span always
    reaches into the current new-grid window (now < cur_old + W_old), so
    a live count is always carried; stale slots zero."""
    live = was["win_start"] == cur_old
    return dict(count=jnp.where(live, was["count"], 0),
                win_start=jnp.where(live, new_start, 0))


def rebucket_sliding(was, cur_old, w_old, new_start, prev_is_current,
                     prev_is_previous):
    """Dynamic window, sliding: the old curr bucket's span always
    overlaps the current new window (as above) -> new curr. Old prev
    lands by its span end (host scalars: ``prev_is_current``, the
    current new window; ``prev_is_previous``, the one before — the
    weighted boundary; neither: aged out)."""
    ws = was["win_start"]
    on_cur = ws == cur_old
    curr = jnp.where(on_cur, was["curr"], 0)
    prev = jnp.where(on_cur, was["prev"],
                     jnp.where(ws == cur_old - w_old, was["curr"], 0))
    new_curr = curr + jnp.where(prev_is_current, prev, 0)
    new_prev = jnp.where(prev_is_previous, prev, 0)
    keep = (new_curr > 0) | (new_prev > 0)
    return dict(curr=jnp.where(keep, new_curr, 0),
                prev=jnp.where(keep, new_prev, 0),
                win_start=jnp.where(keep, new_start, 0))


def _dense_rewrite(state: State, *scalars, algorithm, update):
    names = COLUMNS[algorithm]
    cols = state["cols"]
    k = len(names)
    was = dict(zip(names, directory.join(cols[:k], cols[k:])))
    now = {**was, **update(was, *scalars)}
    rows = jnp.stack([jnp.broadcast_to(jnp.asarray(now[name], jnp.int64),
                                       cols.shape[1:]) for name in names])
    return {**state, "cols": jnp.concatenate(directory.words(rows))}


def build_rewrite(algorithm: Algorithm, update: Callable) -> Callable:
    """Jitted ``rewrite(state, *scalars) -> state`` applying ``update``
    (one of the functions above) to every slot, module
    ``jit_dense_<update>``; state is donated."""
    ensure_x64()
    kw = dict(algorithm=algorithm, update=update)
    return memoized(_BUILT, kw, ("rewrite",), lambda: jax.jit(
        named(f"dense_{update.__name__}", _dense_rewrite, **kw),
        donate_argnums=(0,)))
