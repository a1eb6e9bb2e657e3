"""One transfer in, one program launch and one transfer out per dispatch
(ADR-010 addenda).

The serving steps take ONE staged uint64 buffer ``[ids(P) | n(P) |
now_us(1)]`` and end by packing their verdicts into ONE int32 buffer a
device (``sketch_kernels.pack_window`` / ``bucket_kernels.pack_bucket``);
resolve fetches that buffer and rebuilds BatchResult's int64 and float64
columns on the host. Held here:

* the BatchResult rebuilt from the packed buffer equals the (h1, h2) step
  followed by the OLD finish arithmetic — kept below as the plain NumPy
  reference, in IEEE float64 — all four columns exact, both lanes, with and
  without policy overrides, across a sub-window and the window boundary;
* the step's output is exactly one array a device, int32: no 64-bit and
  no float dtype leaves the device;
* the boundary values survive the word format;
* a launch makes exactly one jitted call and one explicit placement and
  no implicit transfer;
* a resolve records one ``fetch`` span and asks the device for one buffer a
  device.
"""

import contextlib
import sys
import threading
import time
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ratelimiter_tpu import (
    Algorithm,
    Config,
    DenseParams,
    ManualClock,
    SketchParams,
    native,
)
from ratelimiter_tpu.algorithms.dense import DenseLimiter
from ratelimiter_tpu.algorithms.sketch import (
    SketchLimiter,
    SketchTokenBucketLimiter,
)
from ratelimiter_tpu.core.clock import MICROS, to_micros
from ratelimiter_tpu.observability import tracing
from ratelimiter_tpu.ops import (
    bucket_kernels,
    dense_kernels,
    directory,
    sketch_kernels,
)
from ratelimiter_tpu.ops.hashing import split_hash, splitmix64
from tests.parent_lookup import inline_lookup
from tests.parent_writeback import int64_histograms

T0 = 1_700_000_000.25
ALGOS = {"windowed": Algorithm.SLIDING_WINDOW,
         "fixed": Algorithm.FIXED_WINDOW,
         "bucket": Algorithm.TOKEN_BUCKET}
#: Five instants: two in one sub-window, the next sub-window, the last
#: second of the window (it ends at T0 + 39.75), and the next window.
INSTANTS = (0.0, 0.3, 11.0, 39.5, 40.1)
#: The largest limit a test key is given (the config's is 3).
BIG = 50


#: The dense backend rides the same lane (algorithms/hashed_lane.py):
#: the tests that hold the lane itself — one transfer, one program, one
#: int32 buffer, one fetch, the lookup counter — take it as further cases.
DENSE = {"dense-bucket": Algorithm.TOKEN_BUCKET,
         "dense-fixed": Algorithm.FIXED_WINDOW,
         "dense-window": Algorithm.SLIDING_WINDOW}
DENSE_CAPACITY = 256
LANES = list(ALGOS) + list(DENSE)


def _cfg(algo: str, **kw) -> Config:
    base = dict(algorithm={**ALGOS, **DENSE}[algo], limit=3, window=60.0,
                sketch=SketchParams(depth=3, width=512, sub_windows=6),
                dense=DenseParams(capacity=DENSE_CAPACITY, lanes=16))
    base.update(kw)
    return Config(**base)


def _cls(algo: str):
    if algo in DENSE:
        return DenseLimiter
    return SketchTokenBucketLimiter if algo == "bucket" else SketchLimiter


# What the step itself computed until PR 29, as plain NumPy on the (h1,
# h2) step's three outputs: integers in, IEEE float64 out.

def _old_finish_window(allowed, remaining, now_us, window_us):
    cur_ws = (now_us // window_us) * window_us
    reset = np.float64(cur_ws + window_us) / 1e6
    retry = np.where(allowed, np.float64(0.0),
                     np.float64(cur_ws + window_us - now_us) / 1e6)
    return (allowed, remaining.astype(np.int64), retry,
            np.broadcast_to(reset, allowed.shape))


def _old_finish_bucket(allowed, remaining, retry_us, now_us, window_us):
    reset = np.float64(now_us + window_us) / 1e6
    return (allowed, remaining.astype(np.int64),
            retry_us.astype(np.float64) / 1e6,
            np.broadcast_to(reset, allowed.shape))


def _reference(algo, step, state, ids, ns, now_us, padded, premix, cfg,
               policy):
    """The (h1, h2) step on ``state`` and the old finish arithmetic:
    ``(state, the four columns over [:b])``."""
    b = ids.shape[0]
    h1, h2 = split_hash(splitmix64(ids) if premix else ids, cfg.sketch.seed)
    pad = lambda a, dt: np.concatenate(
        [a, np.zeros(padded - b, a.dtype)]).astype(dt)
    state, (allowed, remaining, third) = step(
        state, pad(h1, np.uint32), pad(h2, np.uint32), pad(ns, np.int32),
        jnp.int64(now_us), policy)
    allowed, remaining, third = (np.asarray(x)[:b]
                                 for x in (allowed, remaining, third))
    window_us = to_micros(cfg.window)
    if algo == "bucket":
        return state, _old_finish_bucket(allowed, remaining, third, now_us,
                                         window_us)
    return state, _old_finish_window(allowed, remaining, now_us, window_us)


def _assert_columns_exact(got, want):
    for name, w_ in zip(("allowed", "remaining", "retry_after", "reset_at"),
                        want):
        g = np.asarray(getattr(got, name))
        w_ = np.ascontiguousarray(w_)
        assert g.dtype == w_.dtype and g.shape == w_.shape, name
        # Bit for bit: floats compared as their 64-bit patterns.
        np.testing.assert_array_equal(g.view(np.uint8), w_.view(np.uint8),
                                      err_msg=name)


@pytest.mark.parametrize("overrides", [False, True],
                         ids=["no-overrides", "overrides"])
@pytest.mark.parametrize("b", [8, 1003, 4096])
@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo", list(ALGOS))
def test_rebuilt_result_equals_step_then_old_finish(algo, premix, b,
                                                    overrides):
    cfg = _cfg(algo)
    kernels = bucket_kernels if algo == "bucket" else sketch_kernels
    # The reference: the un-jitted step body over host-split (h1, h2),
    # under the one derivation of its statics.
    body = (bucket_kernels._bucket_step if algo == "bucket"
            else sketch_kernels._sketch_step)
    step = jax.jit(partial(body, **kernels.step_statics(cfg)))
    lim = _cls(algo)(cfg, ManualClock(T0))
    hot = [f"hot:{i}" for i in range(4)]
    if overrides:
        # On the hashed lane these keys are in every frame (remaining
        # reaches BIG - n); on the premix lane the table is there and no
        # id matches it (overrides address string keys).
        lim.set_override(hot[0], BIG)
        lim.set_override(hot[1], 1)
    with lim._lock:
        policy = lim._policy_device()
    ref = kernels.init_state(cfg)
    padded = lim._padded_size(b)

    rng = np.random.default_rng(b + 17 * premix + 5 * overrides)
    admitted = denied = 0
    period = None
    for dt in INSTANTS:
        now = T0 + dt
        now_us = to_micros(now)
        ids = rng.integers(1, max(2, b // 3), size=b).astype(np.uint64)
        ids[:4] = lim._hash(hot)
        ns = rng.integers(1, 3, size=b).astype(np.int64)
        if algo != "bucket" and period != (
                p := now_us // sketch_kernels.sketch_geometry(cfg)[1]):
            period = p                  # the host's _sync_period
            ref = kernels.build_controls(cfg)[1](ref, np.int64(p))
        got = (lim.allow_ids if premix else lim.allow_hashed)(
            ids, ns, now=now)
        ref, want = _reference(algo, step, ref, ids, ns, now_us, padded,
                               premix, cfg, policy)
        _assert_columns_exact(got, want)
        admitted += int(got.allowed.sum())
        denied += b - int(got.allowed.sum())
    assert admitted and denied
    for k, v in lim._state.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(ref[k]))
    lim.close()


# --------------------------------------------- what leaves the device


def _launch(lim, premix, ids, **kw):
    return (lim.launch_ids if premix else lim.launch_hashed)(ids, **kw)


@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo", LANES)
def test_the_step_returns_one_int32_buffer(algo, premix):
    """By the lowered program's own output types, and by the array the
    ticket holds: state leaves aside, one array, int32, rows x P words
    (and, dense, the directory's four tail words)."""
    cfg = _cfg(algo)
    tail = 0
    if algo in DENSE:
        rows, tail = dense_kernels.DENSE_ROWS, directory.TAIL_WORDS
        step = dense_kernels.build_hashed_step(cfg, DENSE_CAPACITY,
                                               premix=premix)
    elif algo == "bucket":
        rows = bucket_kernels.BUCKET_ROWS
        step = bucket_kernels.build_hashed_step(cfg, premix=premix)
    else:
        rows = sketch_kernels.WINDOW_ROWS
        step = sketch_kernels.build_hashed_step(cfg, premix=premix)
    lim = _cls(algo)(cfg, ManualClock(T0))
    with lim._lock:
        policy = lim._policy_device()
    staged = jax.ShapeDtypeStruct((2 * 64 + 1,), jnp.uint64)
    out = step.lower(lim._state, staged, policy).out_info[1]
    assert len(jax.tree_util.tree_leaves(out)) == 1, "one leaf, not a tuple"
    assert out.dtype == jnp.int32 and out.shape == (rows * 64 + tail,)

    ticket = _launch(lim, premix, np.arange(1, 40, dtype=np.uint64))
    assert isinstance(ticket.outs, jax.Array)
    assert ticket.outs.dtype == jnp.int32
    assert ticket.outs.shape == (rows * ticket.padded + tail,)
    assert len(ticket.outs.addressable_shards) == 1
    lim.resolve(ticket)
    lim.close()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("algo", ["windowed", "bucket"])
def test_mesh_steps_return_one_int32_shard_a_device(algo):
    """The replicated mesh's step and the collective router's: one array,
    int32, one shard on each device and nothing else to fetch."""
    from ratelimiter_tpu.core.config import MeshSpec
    from ratelimiter_tpu.parallel import (
        CollectiveMeshLimiter,
        MeshSketchLimiter,
        MeshTokenBucketLimiter,
        make_mesh,
    )

    ids = np.arange(1, 300, dtype=np.uint64)
    mesh = make_mesh(n_devices=8)
    meshed = (MeshTokenBucketLimiter if algo == "bucket"
              else MeshSketchLimiter)(_cfg(algo), ManualClock(T0), mesh=mesh)
    coll = CollectiveMeshLimiter(
        _cfg(algo, mesh=MeshSpec(devices=4, router="collective")),
        ManualClock(T0), n_devices=4)
    for lim, n in ((meshed, 8), (coll, 4)):
        ticket = lim.launch_hashed(ids)
        assert isinstance(ticket.outs, jax.Array)
        assert ticket.outs.dtype == jnp.int32 and ticket.outs.ndim == 1
        shards = ticket.outs.addressable_shards
        assert len({s.device for s in shards}) == len(shards) == n
        before = lim.result_fetches
        res = lim.resolve(ticket)
        assert lim.result_fetches - before == n
        assert res.remaining.dtype == np.int64
        assert res.retry_after.dtype == res.reset_at.dtype == np.float64
        lim.close()


# ------------------------------------------------------ boundary values


@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo", list(ALGOS))
def test_remaining_zero_and_the_largest_limit_survive(algo, premix):
    """``remaining`` at both ends of its range: 0 on the request that
    takes a key's last unit, and the largest limit less one on the first
    request of the key with the largest override (the config's own limit
    on the premix lane, which no override reaches)."""
    top = (1 << 24) - 1 if algo != "bucket" else 4_000_000
    lim = _cls(algo)(_cfg(algo), ManualClock(T0))
    lim.set_override("big", top)
    big = lim._hash(["big"])[0]
    want_first = 2 if premix else top - 1
    ids = np.array([big, 7, 7, 7, 7], dtype=np.uint64)
    res = lim.resolve(_launch(lim, premix, ids))
    assert res.remaining.tolist() == [want_first, 2, 1, 0, 0]
    assert res.allowed.tolist() == [True, True, True, True, False]
    assert res.remaining.dtype == np.int64
    if not premix:
        assert res.limits.tolist() == [top, 3, 3, 3, 3]
    lim.close()


def test_bucket_retry_us_beyond_32_bits_survives_the_word_split():
    """A 30-day window refills one token in 2.6e12 us (> 2**32): the
    denial's retry-after crosses the low/high word split whole."""
    window = 30 * 86400.0
    cfg = _cfg("bucket", limit=1, window=window)
    lim = SketchTokenBucketLimiter(cfg, ManualClock(T0))
    ids = np.array([5, 5, 9], dtype=np.uint64)
    res = lim.allow_hashed(ids)
    assert res.allowed.tolist() == [True, False, True]
    retry_us = int(round(res.retry_after[1] * 1e6))
    assert retry_us == to_micros(window) and retry_us >= 1 << 32
    assert res.retry_after[1] == np.float64(retry_us) / 1e6
    lim.close()


def test_pack_and_unpack_are_inverse_on_the_extremes():
    """The word format alone, on values no small configuration reaches."""
    retry_us = np.array([0, 1, (1 << 32) - 1, 1 << 32, (1 << 32) + 1,
                         (1 << 53) - 1, (1 << 62) + 12345, 5],
                        dtype=np.int64)
    remaining = np.array([0, 1, (1 << 23) - 1, 4_398_046, 7, 0, 2, 3])
    allowed = np.array([1, 0, 1, 1, 0, 0, 1, 0], dtype=bool)
    words = np.asarray(jax.jit(bucket_kernels.pack_bucket)(
        jnp.asarray(allowed), jnp.asarray(remaining), jnp.asarray(retry_us)))
    assert words.dtype == np.int32 and words.shape == (4 * 8,)
    rows, tails = sketch_kernels.result_rows(words, bucket_kernels.BUCKET_ROWS)
    assert tails.shape == (1, 0) and np.shares_memory(rows, words)
    np.testing.assert_array_equal(
        sketch_kernels.join_words(rows[2], rows[3]), retry_us)
    got = bucket_kernels.unpack_bucket(rows, 8, 1_000_000, 60_000_000)
    np.testing.assert_array_equal(got[0], allowed)
    np.testing.assert_array_equal(got[1], remaining)
    np.testing.assert_array_equal(got[2], retry_us.astype(np.float64) / 1e6)
    np.testing.assert_array_equal(got[3], np.full(8, 61.0))

    # Sharded: each shard's rows then its tail; the rows come back in
    # batch order over the shards.
    one = np.arange(2 * 4 + 3, dtype=np.int32)
    rows, tails = sketch_kernels.result_rows(
        np.concatenate([one, one + 100]), 2, shards=2, tail=3)
    assert rows.tolist() == [[0, 1, 2, 3, 100, 101, 102, 103],
                             [4, 5, 6, 7, 104, 105, 106, 107]]
    assert tails.tolist() == [[8, 9, 10], [108, 109, 110]]


# --------------------------------------- the native rebuild and its twin
#
# Resolve rebuilds BatchResult's columns from the fetched words in ONE
# native call (native/hasher.cpp unpack_columns, through
# native.column_unpacker); the formats' NumPy functions are its twin —
# what a host without a compiler serves from, and what the pass is held
# to here, byte for byte.

FORMATS = {"window": (sketch_kernels.WINDOW_ROWS,
                      sketch_kernels.unpack_window),
           "bucket": (bucket_kernels.BUCKET_ROWS,
                      bucket_kernels.unpack_bucket),
           "dense": (dense_kernels.DENSE_ROWS, dense_kernels.unpack_dense)}
UNPACK_PAD = 8192
NOW_US, WINDOW_US = 1_700_000_000_123_457, 60_000_000

needs_native = pytest.mark.skipif(
    not native.native_available(),
    reason="no native extension on this host (no g++, or "
           "RATELIMITER_TPU_NO_BUILD=1): the NumPy twin serves")


def _join(rows, r, v):
    """Write int64 ``v`` as the word pair (r, r + 1) of ``rows``."""
    rows[r] = (v & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    rows[r + 1] = (v >> 32).astype(np.int32)


def _packed_words(fmt: str, values: str, pl: int, shards: int, tail: int,
                  seed: int) -> np.ndarray:
    """A fetch as the device leaves it — ``int32[shards * (rows * pl +
    tail)]`` — filled with the named extreme."""
    n_rows, _ = FORMATS[fmt]
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(shards):
        rows = np.zeros((n_rows, pl), dtype=np.int32)
        if values != "zeros":
            rows[0] = rng.integers(0, 2, pl)
        if values == "largest-limit":
            rows[1] = (1 << 24) - 1 if fmt == "window" else 4_398_046
            rows[1, ::3] = rng.integers(0, 1 << 22, pl)[::3]
        elif values == "beyond-32-bits":
            rows[1] = rng.integers(0, 1 << 22, pl)
            for r in range(2, n_rows, 2):
                big = rng.integers(1 << 32, 1 << 62, pl, dtype=np.int64)
                big[:4] = [(1 << 32) - 1, 1 << 32, (1 << 32) + 1,
                           (1 << 53) + 1]
                _join(rows, r, big)
        elif values == "negative-words":
            # No step ships these; the format's arithmetic still has one
            # answer for them (sign extension, a wrapping int64 sum).
            rows[1:] = rng.integers(-(1 << 31), 1 << 31, (n_rows - 1, pl))
            rows[1:, :2] = [[-1, -(1 << 31)]] * (n_rows - 1)
        out.append(np.concatenate(
            [rows.ravel(), rng.integers(0, 99, tail).astype(np.int32)]))
    return np.concatenate(out)


def _assert_native_equals_twin(fmt, words, shards, tail, b, ns):
    n_rows, twin = FORMATS[fmt]
    rows, tails = sketch_kernels.result_rows(words, n_rows, shards=shards,
                                             tail=tail)
    want = twin(rows, b, NOW_US, WINDOW_US)
    got, admitted = native.column_unpacker(twin)(
        words, shards, tail, b, NOW_US, WINDOW_US, ns)
    for name, w, g in zip(("allowed", "remaining", "retry_after",
                           "reset_at"), want, got):
        assert g.dtype == w.dtype and g.shape == w.shape == (b,), name
        assert g.tobytes() == w.tobytes(), name
    assert [g.dtype for g in got] == [np.bool_, np.int64, np.float64,
                                      np.float64]
    assert type(admitted) is int
    assert admitted == int(ns[want[0]].sum())


@needs_native
@pytest.mark.parametrize("values", ["zeros", "largest-limit",
                                    "beyond-32-bits", "negative-words"])
@pytest.mark.parametrize("b", [1, 8, 499, 501, 4096, UNPACK_PAD - 1,
                               UNPACK_PAD])
@pytest.mark.parametrize("fmt", list(FORMATS))
def test_the_native_pass_builds_the_twins_columns_byte_for_byte(fmt, b,
                                                                values):
    """One shard, as every served dispatch of a slice fetches it: the
    four columns' bytes and dtypes and the admitted mass, on both sides
    of NumPy's 500-element threshold and of the padded size, with the
    tail words each format's steps ship behind the rows."""
    tail = {"window": 2, "bucket": 0, "dense": 4}[fmt]
    words = _packed_words(fmt, values, UNPACK_PAD, 1, tail, seed=b)
    ns = np.random.default_rng(b).integers(1, 1 << 40, b, dtype=np.int64)
    _assert_native_equals_twin(fmt, words, 1, tail, b, ns)


@needs_native
@pytest.mark.parametrize("shards,b", [(2, 9), (4, 32), (4, 25), (8, 4000)])
@pytest.mark.parametrize("fmt", list(FORMATS))
def test_the_native_pass_reads_the_shards_in_batch_order(fmt, shards, b):
    """A fetch of a shard a device (the replicated placement, the
    collective router): rows in batch order over the shards, as
    result_rows' transpose lays them; the tails stay views."""
    pl = -(-b // shards) if b > 100 else 8
    words = _packed_words(fmt, "beyond-32-bits", pl, shards, 3, seed=b)
    ns = np.arange(1, b + 1, dtype=np.int64)
    _assert_native_equals_twin(fmt, words, shards, 3, b, ns)
    # Without ns (no caller wants the mass) the columns are the same.
    _, twin = FORMATS[fmt]
    unpack = native.column_unpacker(twin)
    with_ns, _ = unpack(words, shards, 3, b, NOW_US, WINDOW_US, ns)
    without, mass = unpack(words, shards, 3, b, NOW_US, WINDOW_US, None)
    assert mass == 0
    assert [c.tobytes() for c in with_ns] == [c.tobytes() for c in without]


@needs_native
def test_the_native_pass_refuses_buffers_that_do_not_fit():
    """Every length is checked where the words are read: a short output,
    a fetch that is no whole number of rows, more rows than the words
    hold and an unknown format raise and write nothing out of bounds."""
    unpack_columns = native._load()[1].unpack_columns
    words = np.zeros(2 * 8 + 2, dtype=np.int32)
    cols = lambda b: (np.empty(b, np.bool_), np.empty(b, np.int64),
                      np.empty(b, np.float64), np.empty(b, np.float64))
    ns = np.ones(8, dtype=np.int64)
    assert unpack_columns(0, words, 1, 2, 8, 5, 0.5, 1.5, ns, *cols(8)) == 0
    for args in ((0, words, 1, 2, 8, 5, 0.5, 1.5, ns, *cols(7)),
                 (0, words, 1, 3, 8, 5, 0.5, 1.5, ns, *cols(8)),
                 (0, words, 1, 2, 9, 5, 0.5, 1.5, None, *cols(9)),
                 (0, words, 1, 2, 8, 5, 0.5, 1.5, ns[:7], *cols(8)),
                 (3, words, 1, 2, 8, 5, 0.5, 1.5, ns, *cols(8)),
                 (0, words, 0, 2, 8, 5, 0.5, 1.5, ns, *cols(8))):
        with pytest.raises(ValueError):
            unpack_columns(*args)
    frozen = cols(8)
    frozen[2].flags.writeable = False
    with pytest.raises(ValueError, match="read-only"):
        unpack_columns(0, words, 1, 2, 8, 5, 0.5, 1.5, ns, *frozen)
    assert native.column_unpacker(len) is None      # not a format's twin


def _decide(algo: str):
    """A limiter of lane ``algo`` and the BatchResults of three batches
    through it, the last past a window's boundary."""
    clock = ManualClock(T0)
    lim = _cls(algo)(_cfg(algo), clock)
    ids = np.arange(1, 700, dtype=np.uint64) % 97
    out = []
    for dt in (0.0, 0.3, 61.0):
        clock.set(T0 + dt)
        out.append(lim.resolve(lim.launch_ids(
            ids, np.full(ids.shape[0], 2, dtype=np.int64))))
    return lim, out


@pytest.mark.parametrize("algo", ["windowed", "bucket", "dense-bucket"])
def test_a_host_without_the_extension_serves_from_the_twin(algo,
                                                           monkeypatch):
    """The lane's loader returning None is what native/build.py gives
    without g++ or under RATELIMITER_TPU_NO_BUILD=1: a limiter built
    there decides through the NumPy twin, the same BatchResult, and its
    counter of native rebuilds stays 0."""
    with monkeypatch.context() as m:
        m.setattr(native, "_load", lambda: None)
        plain, want = _decide(algo)
    assert plain._native_unpack is None
    assert plain.result_native_unpacks == 0 and plain.result_fetches == 3
    plain.close()
    if not native.native_available():
        return
    lim, got = _decide(algo)
    assert lim.result_native_unpacks == lim.result_fetches == 3
    for g, w in zip(got, want):
        for col in ("allowed", "remaining", "retry_after", "reset_at"):
            a, b = getattr(g, col), getattr(w, col)
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), col
        assert g.limit == w.limit
    lim.close()


@needs_native
def test_the_native_pass_never_lets_go_of_the_interpreter():
    """No clock: with the switch interval raised past the test's length
    nothing forces the GIL from a thread, so a second thread that only
    counts under it advances only when this one lets go. It does not
    across the native pass on 65,536 rows, and does across the NumPy
    twin's calls on the same words (NumPy releases the GIL around a loop
    of more than 500 elements)."""
    b = 65_536
    n_rows, twin = FORMATS["dense"]
    words = _packed_words("dense", "beyond-32-bits", b, 1, 4, seed=1)
    ns = np.ones(b, dtype=np.int64)
    unpack = native.column_unpacker(twin)
    rows, _ = sketch_kernels.result_rows(words, n_rows, tail=4)
    count, stop = [0], threading.Event()

    def counter():
        while not stop.is_set():
            count[0] += 1
            time.sleep(0)           # hand the interpreter back at once

    interval = sys.getswitchinterval()
    sys.setswitchinterval(60.0)
    thread = threading.Thread(target=counter, daemon=True)
    try:
        thread.start()
        while count[0] == 0:        # the counter is running and waiting
            time.sleep(0.001)
        held = []
        for _ in range(20):
            before = count[0]
            unpack(words, 1, 4, b, NOW_US, WINDOW_US, ns)
            held.append(count[0] - before)
        # The control: the same thread does advance when this one lets
        # go. A release need not be long enough for the waiter to wake,
        # so the twin is given a bounded number of calls to show one.
        released = 0
        for _ in range(500):
            before = count[0]
            twin(rows, b, NOW_US, WINDOW_US)
            released += count[0] - before
            if released:
                break
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        thread.join(timeout=10)
    assert held == [0] * 20
    assert released > 0


# ------------------------------------------------- one launch, one fetch


def test_the_guard_is_live_on_this_backend():
    """The control of the next test: an implicit host->device transfer
    raises under the guard here, an explicit one does not."""
    double = jax.jit(lambda x: x * 2)
    with jax.transfer_guard("disallow"):
        double(jax.device_put(np.arange(4)))
        with pytest.raises(Exception, match="[Dd]isallowed"):
            double(np.arange(4))


@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo, pinned", [
    pytest.param(a, p, id=f"{a}-{'pinned' if p else 'default'}")
    for a in LANES for p in (False, True) if not (p and a in DENSE)])
def test_a_launch_is_one_transfer_and_one_program(algo, premix, pinned,
                                                  monkeypatch):
    device = jax.devices()[-1] if pinned else None
    lim = _cls(algo)(_cfg(algo), ManualClock(T0),
                     **({"device": device} if pinned else {}))
    ids = np.arange(1, 6, dtype=np.uint64)
    lim.resolve(_launch(lim, premix, ids))  # compile, rotate, place the policy

    calls = {"step": 0, "put": 0}
    attr = "_ids_step" if premix else "_step"
    step = getattr(lim, attr)
    real_put = jax.device_put
    stepped = []

    def counted_step(*args):
        calls["step"] += 1
        out = step(*args)
        stepped.append(out[1])
        return out

    def counted_put(*args, **kw):
        calls["put"] += 1
        return real_put(*args, **kw)

    monkeypatch.setattr(lim, attr, counted_step)
    monkeypatch.setattr(jax, "device_put", counted_put)
    with jax.transfer_guard("disallow"):
        ticket = _launch(lim, premix, ids)
    monkeypatch.undo()

    assert calls == {"step": 1, "put": 1}
    # The ticket carries the step's own output: nothing ran after it.
    assert ticket.outs is stepped[0]
    assert ticket.slot.dtype == np.uint64
    assert ticket.slot.shape == (2 * ticket.padded + 1,)
    assert ticket.window_us == to_micros(lim.config.window)
    if pinned:
        assert ticket.outs.devices() == {device}
    out = lim.resolve(ticket)
    assert out.retry_after.dtype == np.float64
    assert out.remaining.dtype == np.int64
    lim.close()


@pytest.fixture
def recorder():
    rec = tracing.enable(capacity=256)
    yield rec
    tracing.disable()


@pytest.mark.parametrize("wire", [False, True], ids=["columns", "wire"])
@pytest.mark.parametrize("algo", LANES)
def test_a_resolve_is_one_fetch_span_and_one_buffer(algo, wire, recorder):
    lim = _cls(algo)(_cfg(algo), ManualClock(T0))
    ids = np.arange(1, 30, dtype=np.uint64)
    for k in range(1, 4):
        ticket = lim.launch_ids(ids, wire=wire)
        ticket.trace_id = 40 + k
        res = lim.resolve(ticket)
        assert lim.resolve(ticket) is res       # idempotent: no second fetch
        assert lim.result_fetches == k
        assert (res.wire_packed is not None) == wire
        spans = [r for r in recorder.dump() if r["stage"] == "fetch"]
        assert len(spans) == k
        assert spans[-1]["batch"] == 29
        assert int(spans[-1]["trace_id"]) == 40 + k
    lim.close()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("router", ["host", "collective"])
def test_mesh_resolves_count_a_shard_a_device(router, recorder):
    """The host router: one buffer per slice dispatch; the collective
    router: four packed shards a frame and nothing else (mass and the
    overflow flag ride in each shard's tail), one span a frame."""
    from ratelimiter_tpu.core.config import MeshSpec
    from ratelimiter_tpu.parallel import (
        CollectiveMeshLimiter,
        SlicedMeshLimiter,
    )

    cls = CollectiveMeshLimiter if router == "collective" \
        else SlicedMeshLimiter
    lim = cls(_cfg("windowed", mesh=MeshSpec(devices=4, router=router)),
              ManualClock(T0), n_devices=4)
    ids = np.arange(1, 400, dtype=np.uint64)
    assert len(set(lim.owner_of_hash(ids).tolist())) == 4
    for k in range(1, 3):
        res = lim.resolve(lim.launch_hashed(ids))
        assert 0 < res.allowed.sum() <= ids.shape[0]
        assert lim.result_fetches == 4 * k <= 9 * k
        spans = [r for r in recorder.dump() if r["stage"] == "fetch"]
        assert len(spans) == (k if router == "collective" else 4 * k)
    lim.close()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo", ["windowed", "bucket"])
def test_mesh_staging_hook_gives_the_single_chip_columns(algo, premix):
    from ratelimiter_tpu.parallel import (
        MeshSketchLimiter,
        MeshTokenBucketLimiter,
        make_mesh,
    )

    cfg = _cfg(algo, limit=5)
    mesh = make_mesh(n_devices=8)
    if algo == "bucket":
        single = SketchTokenBucketLimiter(cfg, ManualClock(T0))
        meshed = MeshTokenBucketLimiter(cfg, ManualClock(T0), mesh=mesh)
    else:
        single = SketchLimiter(cfg, ManualClock(T0))
        meshed = MeshSketchLimiter(cfg, ManualClock(T0), mesh=mesh)
    rng = np.random.default_rng(3)
    for size in (300, 37):              # ragged over 8 chips both times
        ids = rng.integers(1, 40, size=size).astype(np.uint64)
        ns = rng.integers(1, 3, size=size).astype(np.int64)
        # The second launch stages under the guard: the hook's
        # placements are explicit, none is left to the jitted call.
        with (jax.transfer_guard("disallow") if size == 37
              else contextlib.nullcontext()):
            if premix:
                t_mesh = meshed.launch_ids(ids, ns)
            else:
                t_mesh = meshed.launch_hashed(ids, ns)
        a = single.allow_ids(ids, ns) if premix else \
            single.allow_hashed(ids, ns)
        m = meshed.resolve(t_mesh)
        assert 0 < m.allowed.sum() < size
        for col in ("allowed", "remaining", "retry_after", "reset_at"):
            np.testing.assert_array_equal(getattr(a, col), getattr(m, col))
        single.clock.advance(1.5)
        meshed.clock.advance(1.5)
    single.close()
    meshed.close()


# ------------------------- the override lookup's occupancy branch (PR 31)


@contextlib.contextmanager
def _parent_programs(monkeypatch, module, name, reference):
    """Every step built and traced inside is the parent's program:
    ``module.name`` swapped for the test-side ``reference``, the
    builders' memos empty (and the real ones back afterwards)."""
    from ratelimiter_tpu.ops import route_kernels
    from ratelimiter_tpu.parallel import mesh_kernels

    with monkeypatch.context() as m:
        m.setattr(module, name, reference)
        for mod in (sketch_kernels, bucket_kernels, mesh_kernels):
            m.setattr(mod, "_BUILT", {})
        m.setattr(route_kernels, "_ROUTED_CACHE", {})
        yield


def _assert_runs_equal(got, want):
    """Two ``_mesh_run``s: every result column bit for bit (floats as
    their 64-bit patterns) and every state leaf."""
    (got_cols, got_leaves, _), (want_cols, want_leaves, _) = got, want
    assert len(got_cols) == len(want_cols) == 15
    for g, w_ in zip(got_cols, want_cols):
        if w_ is None:
            assert g is None
        else:
            assert g.dtype == w_.dtype
            np.testing.assert_array_equal(g.view(np.uint8), w_.view(np.uint8))
    assert len(got_leaves) == len(want_leaves)
    for g, w_ in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(g, w_)
    allowed = np.concatenate(got_cols[0::5])
    assert allowed.any() and not allowed.all()


def _mesh_limiter(algo, kind):
    from ratelimiter_tpu.core.config import MeshSpec
    from ratelimiter_tpu.parallel import (
        CollectiveMeshLimiter,
        MeshSketchLimiter,
        MeshTokenBucketLimiter,
        make_mesh,
    )

    if kind == "collective":
        return CollectiveMeshLimiter(
            _cfg(algo, mesh=MeshSpec(devices=4, router="collective")),
            ManualClock(T0), n_devices=4)
    cls = MeshTokenBucketLimiter if algo == "bucket" else MeshSketchLimiter
    return cls(_cfg(algo), ManualClock(T0), mesh=make_mesh(n_devices=4),
               merge=kind)


def _mesh_run(algo, kind, overrides, heavy=0):
    """Three frames through a fresh mesh limiter: every result column and
    every state leaf of every dispatch unit at the end. ``heavy``: the
    hottest key's override, and its first row asks for nearly all of it
    at once (more than 2**22 tokens)."""
    lim = _mesh_limiter(algo, kind)
    hot = [f"hot:{i}" for i in range(4)]
    if overrides:
        lim.set_override(hot[0], heavy or BIG)
        lim.set_override(hot[1], 1)
        lim.set_override("never:seen", BIG)
    rng = np.random.default_rng(11)
    cols = []
    for i, dt in enumerate(INSTANTS[:3]):
        ids = rng.integers(1, 90, size=403).astype(np.uint64)
        # Spread over the frame, so that no (source, destination) bin of
        # the collective router overflows: the routed step must decide.
        ids[0:400:25] = lim._hash(hot[:1])[0]
        ids[1:400:100] = lim._hash(hot)
        if i == 2:
            ids[200] = lim._hash(["never:seen"])[0]
        ns = rng.integers(1, 3, size=403).astype(np.int64)
        if heavy:
            ns[0] = heavy - 90_000
        res = lim.allow_hashed(ids, ns, now=T0 + dt)
        cols += [np.asarray(getattr(res, c)) for c in
                 ("allowed", "remaining", "retry_after", "reset_at")]
        cols.append(None if res.limits is None else np.asarray(res.limits))
    units = lim.sub_limiters() if hasattr(lim, "sub_limiters") else [lim]
    leaves = [np.asarray(v) for u in units
              for _, v in sorted(u._state.items())]
    lookups = lim.override_lookup_dispatches
    if kind == "collective":
        assert lim.router_stats()["fallbacks"] == 0
    lim.close()
    return cols, leaves, lookups


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("overrides", [False, True],
                         ids=["empty", "overrides"])
@pytest.mark.parametrize("kind", ["gather", "delta", "collective"])
@pytest.mark.parametrize("algo", ["windowed", "bucket"])
def test_mesh_steps_equal_their_inline_lookup_programs(algo, kind, overrides,
                                                       monkeypatch):
    """The replicated mesh (both merges) and the routed step inside
    shard_map: the table is a replicated operand, every chip takes the
    same branch, and the answers and states are the parent program's."""
    from ratelimiter_tpu.ops import policy_kernels as pk

    with _parent_programs(monkeypatch, pk, "limit_for_rows", inline_lookup):
        want = _mesh_run(algo, kind, overrides)
    got = _mesh_run(algo, kind, overrides)
    _assert_runs_equal(got, want)
    got_cols, _, lookups = got
    if overrides:
        # The hottest key is under BIG: its first row has more left than
        # the config's whole limit.
        assert got_cols[1][0] > 3 and got_cols[4] is not None
    # One count a dispatch (a frame) with an entry, none without.
    assert lookups == (3 if overrides else 0)


# ------------------- the bucket's write-back in 32-bit token units (PR 34)


#: The largest override the bucket takes (its micro-tokens stay under
#: 2**42): a row asking for nearly all of it needs the second limb at
#: these frame sizes.
HEAVY = 4_398_046


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("heavy", [0, HEAVY], ids=["light", "heavy"])
@pytest.mark.parametrize("kind", ["gather", "delta", "collective"])
def test_mesh_bucket_steps_equal_their_int64_write_back_programs(
        kind, heavy, monkeypatch):
    """The replicated mesh (delta: the psum runs on the widened int64
    histograms, as it did) and the routed bucket step: the answers and
    every slice's state are those of the programs that scatter-add int64
    micro-tokens (tests/parent_writeback.py; tests/test_bucket_writeback.py
    holds the single-device step)."""
    with _parent_programs(monkeypatch, bucket_kernels, "_debt_histograms",
                          int64_histograms):
        want = _mesh_run("bucket", kind, True, heavy)
    got = _mesh_run("bucket", kind, True, heavy)
    _assert_runs_equal(got, want)
    got_cols, got_leaves, _ = got
    debt = max(int(g.max()) for g in got_leaves if g.ndim == 2)
    if heavy:
        # The heavy row was admitted once, and its cells hold it.
        assert got_cols[0][0] and not got_cols[5][0]
        assert debt >= (heavy - 90_000) * MICROS
    else:
        assert 0 < debt < 400 * MICROS


@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo", LANES)
def test_the_lookup_counter_follows_the_table(algo, premix):
    """+0 a dispatch on an empty table, +1 with an entry, +0 again after
    the last delete_override; the same compiled program throughout."""
    lim = _cls(algo)(_cfg(algo), ManualClock(T0))
    step = lim._get_ids_step() if premix else lim._step
    ids = np.arange(1, 40, dtype=np.uint64)

    def dispatch(k):
        before = lim.override_lookup_dispatches
        for _ in range(k):
            lim.resolve(_launch(lim, premix, ids))
        return lim.override_lookup_dispatches - before

    assert lim.override_lookup_dispatches == 0
    assert dispatch(2) == 0
    programs = step._cache_size()
    lim.set_override("vip", BIG)
    assert dispatch(3) == 3
    lim.set_override("vip2", 1)
    assert lim.delete_override("vip")
    assert dispatch(1) == 1
    assert lim.delete_override("vip2")
    assert dispatch(2) == 0
    assert lim.override_lookup_dispatches == 4
    # The first override compiled nothing: one program a shape as before
    # (the memoized step is shared, so the count is this process's).
    assert step._cache_size() == programs >= 1
    lim.close()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("router", ["host", "collective"])
def test_mesh_lookup_counter_is_a_count_a_dispatch_or_a_frame(router):
    """Host router: one a slice dispatch (the slices' own counts,
    summed). Collective router: one a frame, the router's own."""
    from ratelimiter_tpu.core.config import MeshSpec
    from ratelimiter_tpu.parallel import (
        CollectiveMeshLimiter,
        SlicedMeshLimiter,
    )

    cls = CollectiveMeshLimiter if router == "collective" \
        else SlicedMeshLimiter
    lim = cls(_cfg("windowed", mesh=MeshSpec(devices=4, router=router)),
              ManualClock(T0), n_devices=4)
    ids = np.arange(1, 400, dtype=np.uint64)
    assert len(set(lim.owner_of_hash(ids).tolist())) == 4
    per_frame = 1 if router == "collective" else 4
    lim.resolve(lim.launch_hashed(ids))
    assert lim.override_lookup_dispatches == 0
    lim.set_override("vip", BIG)
    for k in range(1, 3):
        lim.resolve(lim.launch_hashed(ids))
        assert lim.override_lookup_dispatches == per_frame * k
    assert lim.delete_override("vip")
    lim.resolve(lim.launch_hashed(ids))
    assert lim.override_lookup_dispatches == per_frame * 2
    lim.close()


def test_metrics_export_the_lookup_counter_at_scrape():
    from ratelimiter_tpu.observability import MetricsDecorator
    from ratelimiter_tpu.observability.metrics import Registry

    reg = Registry()
    base = SketchLimiter(_cfg("windowed"), ManualClock(T0))
    lim = MetricsDecorator(base, registry=reg)
    name = "rate_limiter_override_lookup_dispatches_total"

    def scraped():
        lines = [ln for ln in reg.render().splitlines()
                 if ln.startswith(name)]
        assert len(lines) == 1
        return float(lines[0].split()[-1])

    ids = np.arange(1, 9, dtype=np.uint64)
    lim.allow_hashed(ids)
    assert scraped() == 0.0
    lim.set_override("vip", BIG)
    lim.allow_hashed(ids)
    lim.allow_hashed(ids)
    assert scraped() == 2.0
    lim.delete_override("vip")
    lim.allow_hashed(ids)
    assert scraped() == 2.0
    lim.close()
