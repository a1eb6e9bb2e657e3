"""The sketched token bucket writes its debt back from 32-bit token
counts (PR 34): ``bucket_kernels._debt_histograms`` scatters whole
tokens as 32-bit limbs and widens the (d, w) slab once, where the step
scatter-added int64 micro-token amounts, one serialized 64-bit index at
a time on the TPU. Held here, against that int64 write-back kept as
``tests/parent_writeback.int64_histograms``:

* the histograms themselves, for every value an integer operand can
  hold — negative ones and sums past 2**31 in one cell included — and for
  batch sizes on both sides of each limb count;
* the serving step: the packed verdicts (allowed, remaining, retry_us)
  and every state leaf over consecutive steps whose instants cross a
  partial and a whole refill, both lanes, three batch sizes, every kind
  of ``n`` the wire can carry (under overrides that admit the large
  ones), keys colliding in one cell;
* the cascade arm: the debt is written under the FINAL mask;
* the program: no 64-bit scatter is left in the step, the reference has
  one a row, and the limb count follows the batch's shape alone.

The replicated mesh and the routed step are held to the same reference
in tests/test_finished_step.py.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ratelimiter_tpu import (
    Algorithm,
    Config,
    HierarchySpec,
    ManualClock,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.core.clock import MICROS, to_micros
from ratelimiter_tpu.ops import bucket_kernels, policy_kernels as pk
from ratelimiter_tpu.ops.hashing import split_hash, splitmix64_inv
from tests.parent_writeback import int64_histograms

T0 = 1_700_000_000.25
I32_MAX = (1 << 31) - 1
LIMIT = 3
#: Four instants of one run: the same microsecond twice over, a partial
#: refill (11 s of a 60 s window: half a token) and a whole one.
INSTANTS = (0.0, 0.0, 11.0, 75.0)
#: Whole tokens a cell refills over INSTANTS, rounded up.
REFILLED = 4
#: Hot keys the override table admits ``I32_MAX`` tokens to: enough of
#: them that several share a cell of a 512-wide row.
N_HOT = 48


def _cfg(**kw) -> Config:
    base = dict(algorithm=Algorithm.TOKEN_BUCKET, limit=LIMIT, window=60.0,
                sketch=SketchParams(depth=3, width=512, sub_windows=6))
    base.update(kw)
    return Config(**base)


# ------------------------------------------------------- the histograms


def _scatter_adds(fn, *args):
    """dtype of every scatter-add's operand in ``fn``'s jaxpr, through
    every nested jaxpr (the lookup's cond, a pjit)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("scatter-add", "scatter_add"):
                found.append(eqn.invars[0].aval.dtype)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _values(kind, b, rng, dtype):
    info = np.iinfo(dtype)
    if kind == "ones":
        return np.ones(b, dtype)
    if kind == "max":
        return np.full(b, info.max, dtype)
    if kind == "min":
        return np.full(b, info.min, dtype)
    if kind == "limb-edges":
        return rng.choice(np.array([0, 1, 65_535, 65_536, 65_537,
                                    (1 << 19) - 1, 1 << 19, I32_MAX,
                                    -1, -65_536, -I32_MAX - 1]),
                          size=b).astype(dtype)
    return rng.integers(info.min, info.max, size=b, dtype=dtype,
                        endpoint=True)


#: One jitted function each: the kinds of value share a shape's program.
_HISTOGRAMS = jax.jit(partial(bucket_kernels._debt_histograms, w=16))
_PARENT_HISTOGRAMS = jax.jit(partial(int64_histograms, w=16))


@pytest.mark.parametrize("kind", ["ones", "max", "min", "limb-edges",
                                  "any"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64],
                         ids=["int32", "int64"])
@pytest.mark.parametrize("b", [1, 8, 1003, 4096, 32_768, 32_769])
def test_the_histograms_equal_the_int64_scatter_add(b, dtype, kind):
    """Equal in wrapping int64 arithmetic whatever the operand holds;
    the columns are few, so every cell sums many rows."""
    d, w = 3, 16
    rng = np.random.default_rng(b)
    cols = rng.integers(0, 4, size=(b, d)).astype(np.int32)
    cols[:, 0] = 5                      # every row in ONE cell
    tokens = _values(kind, b, rng, dtype)
    got = _HISTOGRAMS(cols, tokens)
    want = _PARENT_HISTOGRAMS(cols, tokens)
    assert got.dtype == want.dtype == jnp.int64 and got.shape == (d, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The reference against plain Python integers, mod 2**64.
    cell = sum(int(t) for t in tokens) * MICROS
    assert int(want[0, 5]) == (cell + (1 << 63)) % (1 << 64) - (1 << 63)


@pytest.mark.parametrize("b, limbs", [(1, 2), (2, 2), (4096, 2),
                                      (32_768, 2), (32_769, 3),
                                      (1 << 20, 3), ((1 << 20) + 1, 4)])
def test_the_limb_count_is_read_off_the_shape(b, limbs):
    """int32 tokens: two 32-bit scatter-adds a row up to B = 32,768 (the
    low limb's sum stays under 2**31), then three; none is 64-bit."""
    d, w = 4, 64
    adds = _scatter_adds(
        partial(bucket_kernels._debt_histograms, w=w),
        jax.ShapeDtypeStruct((b, d), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32))
    assert adds == [jnp.int32] * (d * limbs)
    # No cell's limb sum can wrap: B * (2**bits - 1) < 2**31.
    bits = 31 - (b - 1).bit_length()
    assert b * ((1 << bits) - 1) < 1 << 31 and limbs == -(-32 // bits)


# ------------------------------------------------------ the serving step


def _table(hashes, limits, seed):
    """The device's override table (policy/table.py's layout) for
    finalized 64-bit hashes, built by hand: the limiter's own
    ``set_override`` refuses a limit the packed ``remaining`` word could
    not hold, and these tests want every ``n`` of the wire admitted."""
    keys = pk.pack_halves_host(*split_hash(hashes, seed))
    order = np.argsort(keys)
    cap = 64
    key = np.full(cap, pk.PAD_KEY, np.int64)
    lim = np.full(cap, LIMIT, np.int64)
    key[:len(keys)] = keys[order]
    lim[:len(keys)] = np.asarray(limits, np.int64)[order]
    return {"key": jnp.asarray(key), "limit": jnp.asarray(lim)}


def _empty_table():
    return _table(np.zeros(0, np.uint64), [], 0)


#: kind of ``n`` -> (value or (low, high) drawn per row, override table)
KINDS = {
    "one": (1, False),
    "mixed-1-5": ((1, 5), False),
    "limit": (LIMIT, False),
    "limit-plus-1": (LIMIT + 1, False),
    "65535": (65_535, True),
    "65536": (65_536, True),
    "i32-max": (I32_MAX, True),
    "mixed-wire": (None, True),
}


def _frames(b, kind, rng, hot):
    """``b`` ids and ``n``s an instant: a quarter of each frame is the
    hottest key, the other hot keys ride along where there is room, the
    rest are drawn from few keys (most cells take several)."""
    value, _ = KINDS[kind]
    for _ in INSTANTS:
        ids = rng.integers(1, max(4, b // 3), size=b).astype(np.uint64)
        ids[: max(2, b // 4)] = hot[0]
        k = min(len(hot), b // 2)
        ids[-k:] = hot[:k]
        if value is None:
            ns = rng.choice(np.array([1, 2, 5, 65_535, 65_536, 70_000,
                                      1 << 20, I32_MAX]), size=b)
        elif isinstance(value, tuple):
            ns = rng.integers(value[0], value[1], size=b, endpoint=True)
        else:
            ns = np.full(b, value)
        yield ids, ns.astype(np.int64)


_PARENT_STEPS = {}


def _parent_step(cfg, premix, monkeypatch):
    """The serving step's body traced with the int64 write-back: ONE
    jitted function a lane, so the kinds of ``n`` share its programs
    (every call: a new shape traces again, under the patch)."""
    if premix not in _PARENT_STEPS:
        _PARENT_STEPS[premix] = jax.jit(partial(
            bucket_kernels._bucket_step_staged, seed=cfg.sketch.seed,
            premix=premix, **bucket_kernels.step_statics(cfg)))

    def step(*args):
        with monkeypatch.context() as m:
            m.setattr(bucket_kernels, "_debt_histograms", int64_histograms)
            return _PARENT_STEPS[premix](*args)
    return step


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("b", [8, 1003, 4096])
@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
def test_step_equals_step_with_the_int64_write_back(premix, b, kind,
                                                    monkeypatch):
    cfg = _cfg()
    lim = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
    padded = lim._padded_size(b)
    hot = lim._hash([f"hot:{i}" for i in range(N_HOT)])
    lim.close()
    policy = (_table(hot, [I32_MAX] * N_HOT, cfg.sketch.seed)
              if KINDS[kind][1] else _empty_table())
    new = bucket_kernels.build_hashed_step(cfg, premix=premix)
    old = _parent_step(cfg, premix, monkeypatch)
    s_new = bucket_kernels.init_state(cfg)
    s_old = bucket_kernels.init_state(cfg)
    rng = np.random.default_rng(b + 7 * premix)
    admitted = denied = 0
    hot_allowed = []
    for dt, (ids, ns) in zip(INSTANTS, _frames(b, kind, rng, hot)):
        slot = np.zeros(2 * padded + 1, np.uint64)
        slot[:b] = splitmix64_inv(ids) if premix else ids
        slot.view(np.int64)[padded:padded + b] = ns
        slot.view(np.int64)[2 * padded] = to_micros(T0 + dt)
        s_new, w_new = new(s_new, slot, policy)
        s_old, w_old = old(s_old, slot, policy)
        w_new, w_old = np.asarray(w_new), np.asarray(w_old)
        assert w_new.dtype == np.int32
        assert w_new.shape == (bucket_kernels.BUCKET_ROWS * padded,)
        np.testing.assert_array_equal(w_new, w_old)
        assert sorted(s_new) == sorted(s_old) == ["acc", "debt", "last",
                                                  "rem"]
        for k in s_old:
            np.testing.assert_array_equal(np.asarray(s_new[k]),
                                          np.asarray(s_old[k]), err_msg=k)
        allowed = w_new[:b] != 0
        admitted += int(allowed.sum())
        denied += b - int(allowed.sum())
        hot_allowed.append(int(allowed[: max(2, b // 4)].sum()))
    debt = np.asarray(s_new["debt"])
    if kind == "limit-plus-1":
        # No row fits a bucket of 3: nothing is ever written.
        assert admitted == 0 and not debt.any()
        return
    assert admitted and denied
    assert debt.min() >= 0 and debt.max() > 0
    if kind in ("65535", "65536"):
        # The hottest key's rows are admitted together until I32_MAX
        # tokens are spent (32,768 rows would be): one cell takes their
        # sum, past what a 16-bit limb — and, at 4,096 rows, a 32-bit
        # cell of micro-tokens many times over — could hold.
        assert hot_allowed[0] == max(2, b // 4)
        assert debt.max() > (hot_allowed[0] * 65_535 - REFILLED) * MICROS
    if kind == "i32-max":
        # One row a hot key and, where the frame has room for all of
        # them, several hot keys in one cell: its whole-token sum passes
        # 2**31.
        assert hot_allowed[0] == 1
        assert debt.max() > ((1 + (b > N_HOT)) * I32_MAX
                             - REFILLED) * MICROS


# ------------------------------------------------------- the cascade arm


def _cascade_run():
    """Frames a tenant's window cannot hold through a fresh limiter with
    the cascade on: the result columns of every frame and the state."""
    cfg = _cfg(limit=50, hierarchy=HierarchySpec(
        tenants=4, map_capacity=64, global_limit=40,
        default_tenant_limit=30))
    clock = ManualClock(T0)
    lim = create_limiter(cfg, backend="sketch", clock=clock)
    lim.set_tenant("a", 9)
    lim.set_tenant("b", 14)
    keys = [f"k{i}" for i in range(24)]
    for i, k in enumerate(keys):
        if i % 3:
            lim.assign_tenant(k, "ab"[i % 3 - 1])
    rng = np.random.default_rng(5)
    cols, admitted = [], 0
    for dt in (0.0, 0.5, 20.0, 61.0):
        clock.set(T0 + dt)
        batch = [keys[i] for i in rng.integers(0, len(keys), size=97)]
        ns = rng.integers(1, 4, size=97).tolist()
        res = lim.allow_batch(batch, ns)
        cols += [np.asarray(getattr(res, c)) for c in
                 ("allowed", "remaining", "retry_after", "reset_at")]
        admitted += int(np.asarray(ns)[np.asarray(res.allowed)].sum())
    state = {k: np.asarray(v) for k, v in lim._state.items()}
    stats = lim.hierarchy_stats()
    lim.close()
    return cols, state, stats, admitted


def test_the_cascade_writes_debt_under_its_final_mask(monkeypatch):
    """Rows the key scope admits and a tenant or the global scope then
    denies must add nothing: the 32-bit operand is taken from the mask
    the cascade leaves, as the int64 amounts were."""
    with monkeypatch.context() as m:
        m.setattr(bucket_kernels, "_debt_histograms", int64_histograms)
        m.setattr(bucket_kernels, "_BUILT", {})
        want_cols, want_state, _, _ = _cascade_run()
    got_cols, got_state, stats, admitted = _cascade_run()
    for g, w_ in zip(got_cols, want_cols):
        assert g.dtype == w_.dtype
        np.testing.assert_array_equal(g.view(np.uint8), w_.view(np.uint8))
    assert sorted(got_state) == ["acc", "debt", "last", "rem", "tn_counts",
                                 "tn_period"]
    for k in want_state:
        np.testing.assert_array_equal(got_state[k], want_state[k], err_msg=k)
    # The cascade decided: every key's own bucket (50) had room for its
    # first frame's rows, the scopes above it did not.
    first = got_cols[0]
    assert first.any() and not first.all()
    assert stats["global"]["in_window"] <= 40
    # Each sketch row was handed the tokens of the rows the cascade let
    # through and no others (the global scope's 40 a window, and the
    # frames fall in two windows).
    assert 0 < admitted <= 2 * 40
    assert got_state["acc"].sum(axis=1).tolist() == [admitted * MICROS] * 3


# ----------------------------------------------------------- the program


@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
def test_no_64_bit_scatter_is_left_in_the_step(premix, monkeypatch):
    cfg = _cfg()
    kw = bucket_kernels.step_statics(cfg)
    # A new function object a trace: make_jaxpr remembers one it has seen.
    body = lambda: partial(bucket_kernels._bucket_step_staged,
                           seed=cfg.sketch.seed, premix=premix, **kw)
    args = (bucket_kernels.init_state(cfg),
            jax.ShapeDtypeStruct((2 * 4096 + 1,), jnp.uint64),
            _empty_table())
    assert _scatter_adds(body(), *args) == [jnp.int32] * (2 * kw["d"])
    # ... and the reference of these tests is the parent's: one int64
    # scatter-add a row.
    with monkeypatch.context() as m:
        m.setattr(bucket_kernels, "_debt_histograms", int64_histograms)
        assert _scatter_adds(body(), *args) == [jnp.int64] * kw["d"]
