"""One table access a run of equal keys (ops/sortmerge._use_run_dedup) vs
the per-row body: the same bits.

The mechanism only activates on a TPU at padded batches of 8,192 rows and
more, so — as tests/test_sortmerge.py does for ``_use_sortmerge`` — the
static predicate is forced both ways here and the two traced programs are
run on the same state and the same batches: ``allowed``, ``remaining``,
``est`` and every leaf of the state must be equal bit for bit. The chunk
of the access loops is forced down to 8 heads so a 64-row batch crosses
chunk boundaries.
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ratelimiter_tpu import Algorithm, Config, HierarchySpec, SketchParams
from ratelimiter_tpu.ops import policy_kernels, sketch_kernels, sortmerge

B = 64
CHUNK = 8
D, W, SUB = 3, 256, 6
NOW_US = 1_700_000_000_000_000 + 400_000     # 0.4 of a sub-window in


@contextmanager
def forced(dedup: bool, chunk: int = CHUNK):
    saved = sortmerge._use_run_dedup, sortmerge.run_chunk
    sortmerge._use_run_dedup = lambda B_, w_: dedup
    sortmerge.run_chunk = lambda B_: chunk
    try:
        yield
    finally:
        sortmerge._use_run_dedup, sortmerge.run_chunk = saved


VARIANTS = {
    "sliding-cu": dict(),
    "sliding-vanilla": dict(cu=False),
    "fixed-cu": dict(algorithm=Algorithm.FIXED_WINDOW),
    "fixed-vanilla": dict(algorithm=Algorithm.FIXED_WINDOW, cu=False),
    "hh": dict(hh_slots=16),
    "hh-vanilla": dict(hh_slots=16, cu=False),
    "tenants": dict(tenants=4),
    "tenants-vanilla": dict(tenants=4, cu=False),
    "hh-tenants": dict(hh_slots=16, tenants=4),
}


def _cfg(algorithm=Algorithm.TPU_SKETCH, cu=True, hh_slots=0, tenants=0):
    return Config(
        algorithm=algorithm, limit=10, window=6.0,
        max_batch_admission_iters=4,
        sketch=SketchParams(depth=D, width=W, sub_windows=SUB,
                            hh_slots=hh_slots, hh_promote_fraction=0.5,
                            conservative_update=cu),
        hierarchy=HierarchySpec(tenants=tenants, map_capacity=16))


def _state(cfg, seed=5):
    """A state mid-window: counts in every table, the boundary slab valid
    (its weight is fractional at NOW_US), a promoted heavy hitter."""
    rng = np.random.default_rng(seed)
    kw = sketch_kernels.step_statics(cfg)
    st = {k: np.asarray(v).copy()
          for k, v in sketch_kernels.init_state(cfg).items()}
    p = NOW_US // kw["sub_us"]
    st["last_period"] = np.int64(p)
    for q in range(p - kw["SW"], p):
        st["slab_period"][q % kw["S"]] = q
    for name in ("cur", "totals", "slabs"):
        st[name] = rng.integers(0, 4, st[name].shape).astype(np.int32)
    st["totals"] += st["cur"]
    if kw["hh"]:
        st["hh_totals"] = rng.integers(0, 3, st["hh_totals"].shape
                                       ).astype(np.int32)
    return {k: jnp.asarray(v) for k, v in st.items()}


def _pairs(ids):
    """(h1, h2) of integer key ids: distinct halves a key, like the
    hash split's."""
    ids = np.asarray(ids, np.uint64)
    h = (ids + np.uint64(1)) * np.uint64(0x9E3779B97F4A7C15)
    return ((h >> np.uint64(32)).astype(np.uint32),
            (h & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _distinct_then_repeats(runs):
    """``runs`` distinct keys, then repeats of the first ones to fill."""
    return np.concatenate([np.arange(runs), np.arange(B - runs) % runs])


def _batches():
    rng = np.random.default_rng(11)
    ones = np.ones(B, np.int32)
    out = {}
    out["one-key"] = (*_pairs(np.full(B, 7)), ones)
    out["all-distinct"] = (*_pairs(np.arange(B)), ones)
    zipf = np.minimum(rng.zipf(1.1, B), 1 << 18)
    out["zipf"] = (*_pairs(zipf), ones)
    # Two keys that share h1 and differ in h2, interleaved, among others.
    h1, h2 = _pairs(rng.integers(0, 12, B))
    twin = np.arange(B) % 4 == 0
    h1[twin], h2[twin] = 0xABCD1234, 1
    twin2 = np.arange(B) % 4 == 2
    h1[twin2], h2[twin2] = 0xABCD1234, 2
    out["shared-h1"] = (h1, h2, ones.copy())
    # Mixed n, and a padded tail: id 0's pair with n = 0.
    h1, h2 = _pairs(rng.integers(1, 9, B))
    n = rng.integers(1, 5, B).astype(np.int32)
    h1[48:], h2[48:] = _pairs(np.zeros(16))
    n[48:] = 0
    out["mixed-n-padded"] = (h1, h2, n)
    # The run count around a chunk boundary (chunk = 8) and two of them.
    for runs in (CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 2 * CHUNK + 1):
        h1, h2 = _pairs(_distinct_then_repeats(runs))
        order = np.argsort(h1.view(np.int32), kind="stable")
        # as many (h1, h2) runs in sorted order as distinct keys
        assert int(np.sum(np.diff(h1[order]) != 0)) + 1 == runs
        out[f"runs-{runs}"] = (h1, h2, ones)
    return out


BATCHES = _batches()
_STEPS: dict = {}


def _hier(cfg):
    if not cfg.hierarchy.tenants:
        return None
    # Keys 1..6 of the tests' id space on tenants 1..3; tight tenant and
    # global limits so the cascade flips verdicts.
    h1, h2 = _pairs(np.arange(1, 7))
    keys = policy_kernels.pack_halves_host(h1, h2)
    order = np.argsort(keys)
    table = np.full(16, policy_kernels.PAD_KEY, np.int64)
    tids = np.zeros(16, np.int64)
    table[:6] = keys[order]
    tids[:6] = (1 + np.arange(6) % 3)[order]
    T = cfg.hierarchy.tenants
    return {"key": jnp.asarray(table), "tid": jnp.asarray(tids),
            "limit": jnp.asarray(np.array([9, 5, 7, 6, 30], np.int64)[:T + 1]),
            "weight": jnp.asarray(np.array([1, 2, 1, 3, 1], np.int64)[:T + 1])}


def _step(variant: str, dedup: bool):
    """The jitted body for a variant, traced with the predicate forced."""
    key = (variant, dedup)
    if key not in _STEPS:
        cfg = _cfg(**VARIANTS[variant])
        fn = jax.jit(partial(sketch_kernels._sketch_step,
                             **sketch_kernels.step_statics(cfg)))
        hier = _hier(cfg)
        h1, h2, n = BATCHES["zipf"]
        with forced(dedup):
            fn(_state(cfg), h1, h2, n, np.int64(NOW_US), None, hier)
        _STEPS[key] = (fn, cfg, hier)
    return _STEPS[key]


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_the_run_merged_step_is_the_per_row_step_bit_for_bit(variant, batch):
    h1, h2, n = BATCHES[batch]
    results = []
    for dedup in (False, True):
        fn, cfg, hier = _step(variant, dedup)
        state = _state(cfg)
        outs = []
        # Two dispatches, so the second reads what the first wrote.
        for k in range(2):
            state, out = fn(state, h1, h2, n, np.int64(NOW_US + 1000 * k),
                            None, hier)
            outs.append(out)
        results.append((state, outs))
    (state_a, outs_a), (state_b, outs_b) = results
    for a, b in zip(outs_a, outs_b):
        for name, x, y in zip(("allowed", "remaining", "est"), a, b):
            np.testing.assert_array_equal(
                np.asarray(x).view(np.uint8), np.asarray(y).view(np.uint8),
                err_msg=name)
    assert set(state_a) == set(state_b)
    for leaf in state_a:
        np.testing.assert_array_equal(np.asarray(state_a[leaf]),
                                      np.asarray(state_b[leaf]), err_msg=leaf)
    # The case is not vacuous: something was allowed and written.
    assert np.asarray(outs_a[0][0]).any()
    assert int(np.asarray(state_a["cur"]).sum()) > int(
        np.asarray(_state(_STEPS[(variant, False)][1])["cur"]).sum())


@pytest.mark.parametrize("chunk", [1, 8, 64, 2048])
def test_any_chunk_gives_the_same_bits(chunk):
    """The chunk is a cost knob: whole batch in one chunk, a chunk larger
    than the batch, one head a chunk."""
    cfg = _cfg()
    kw = sketch_kernels.step_statics(cfg)
    h1, h2, n = BATCHES["zipf"]
    want = jax.jit(partial(sketch_kernels._sketch_step, **kw))(
        _state(cfg), h1, h2, n, np.int64(NOW_US), None, None)
    with forced(True, chunk):
        got = jax.jit(partial(sketch_kernels._sketch_step, **kw))(
            _state(cfg), h1, h2, n, np.int64(NOW_US), None, None)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_override_lookup_rides_the_sorted_rows():
    """A per-key override is looked up from the rows' own (h1, h2), in
    whichever order the body holds them."""
    cfg = _cfg()
    kw = sketch_kernels.step_statics(cfg)
    h1, h2, n = BATCHES["zipf"]
    keys = policy_kernels.pack_halves_host(h1[:3], h2[:3])
    order = np.argsort(keys)
    table = policy_kernels.empty_arrays(8, {"limit": cfg.limit})
    table["key"][:3] = keys[order]
    table["limit"][:3] = np.array([1, 50, 3])[order]
    policy = {k: jnp.asarray(v) for k, v in table.items()}
    outs = []
    for dedup in (False, True):
        with forced(dedup):
            outs.append(jax.jit(partial(sketch_kernels._sketch_step, **kw))(
                _state(cfg), h1, h2, n, np.int64(NOW_US), policy, None))
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_tail_words_count_rows_and_runs():
    """The serving step's packed result ends with [padded rows, runs] on
    the programs that carry the mechanism, and has no tail on the
    others."""
    cfg = _cfg()
    kw = sketch_kernels.step_statics(cfg)
    # 8 rows by hand: key 5 x3, key 9 x2, then two keys that share h1
    # (interleaved: three runs), sorted order has 1 + 1 + 3 = 5 runs.
    h64 = np.array([5, 9, 5, 9, 5, 0, 0, 0], np.uint64)
    P = 8
    staged = np.zeros(2 * P + 1, np.uint64)
    staged[:P] = h64
    staged[P:2 * P] = [1, 1, 1, 1, 1, 0, 0, 0]
    staged[2 * P] = NOW_US
    from ratelimiter_tpu.ops.hashing import split_hash

    h1, h2 = split_hash(h64, cfg.sketch.seed)
    h1, h2 = np.array(h1), np.array(h2)
    order = np.argsort(h1.view(np.int32), kind="stable")
    pair = np.stack([h1[order], h2[order]])
    want_runs = 1 + int(np.any(pair[:, 1:] != pair[:, :-1], axis=0).sum())
    assert want_runs == 3                       # keys 5, 9 and the padding
    policy = {k: jnp.asarray(v) for k, v in policy_kernels.empty_arrays(
        8, {"limit": cfg.limit}).items()}

    def packed(dedup):
        with forced(dedup):
            step = jax.jit(partial(sketch_kernels._sketch_step_staged,
                                   seed=cfg.sketch.seed, premix=False, **kw))
            return np.asarray(step(_state(cfg), staged, policy)[1])

    plain, counted = packed(False), packed(True)
    assert plain.shape == (sketch_kernels.WINDOW_ROWS * P,)
    assert counted.shape == (sketch_kernels.WINDOW_ROWS * P
                             + sketch_kernels.RUN_TAIL_WORDS,)
    rows, tails = sketch_kernels.result_rows(
        counted, sketch_kernels.WINDOW_ROWS,
        tail=sketch_kernels.RUN_TAIL_WORDS)
    np.testing.assert_array_equal(rows.reshape(-1), plain)
    assert tails.tolist() == [[P, want_runs]]


@pytest.mark.parametrize("B_,w,want", [
    (4096, 1 << 20, False),        # every 4,096-batch cell: the parent's
    (4096, 1 << 16, False),
    (8192, 1 << 20, True),
    (65536, 1 << 20, True),
    (131072, 1 << 20, True),
    (524288, 1 << 20, False),      # B >= w/2: the sort-merge regime
    (65536, 1 << 16, False),
])
def test_the_predicate_reads_the_shape_and_the_platform(monkeypatch, B_, w,
                                                        want):
    assert sortmerge._use_run_dedup(B_, w) is False      # CPU: never
    monkeypatch.setattr(sortmerge, "on_tpu", lambda: True)
    assert sortmerge._use_run_dedup(B_, w) is want
    assert sketch_kernels.step_tail_words(B_, w) == (
        sketch_kernels.RUN_TAIL_WORDS if want else 0)


# ------------------------------------------------ the limiter's counters

@pytest.fixture
def forced_limiter(monkeypatch):
    """A SketchLimiter whose steps carry the mechanism at any batch: the
    predicate forced, the builders' memo emptied for the test (a forced
    program must not be served to another test's limiter)."""
    from ratelimiter_tpu import ManualClock
    from ratelimiter_tpu.algorithms.sketch import SketchLimiter

    monkeypatch.setattr(sketch_kernels, "_BUILT", {})
    monkeypatch.setattr(sortmerge, "_use_run_dedup", lambda B_, w_: True)
    monkeypatch.setattr(sortmerge, "run_chunk", lambda B_: CHUNK)
    lim = SketchLimiter(_cfg(), ManualClock(1_700_000_000.0))
    yield lim
    lim.close()


def test_the_limiter_sums_the_tail_words_and_answers_as_before(
        forced_limiter):
    from ratelimiter_tpu import ManualClock
    from ratelimiter_tpu.algorithms.sketch import SketchLimiter

    ids = np.array([5, 9, 5, 9, 5], np.uint64)      # padded to 8 rows
    assert forced_limiter.table_access_stats == {"rows": 0, "runs": 0}
    got = forced_limiter.allow_hashed(ids)
    # Keys 5 and 9 and the padding's key: three runs of eight rows.
    assert forced_limiter.table_access_stats == {"rows": 8, "runs": 3}
    tickets = [forced_limiter.launch_ids(ids) for _ in range(2)]
    for t in tickets:
        forced_limiter.resolve(t)
    assert forced_limiter.table_access_stats == {"rows": 24, "runs": 9}
    assert forced_limiter.result_fetches == 3       # still one a dispatch
    sortmerge._use_run_dedup = lambda B_, w_: False  # (the fixture restores)
    sketch_kernels._BUILT.clear()
    plain = SketchLimiter(_cfg(), ManualClock(1_700_000_000.0))
    want = plain.allow_hashed(ids)
    assert plain.table_access_stats == {"rows": 0, "runs": 0}
    plain.close()
    for col in ("allowed", "remaining", "retry_after", "reset_at"):
        np.testing.assert_array_equal(getattr(got, col), getattr(want, col))


def test_metrics_export_the_access_counters_at_scrape(forced_limiter):
    from ratelimiter_tpu.observability import MetricsDecorator
    from ratelimiter_tpu.observability.metrics import Registry

    reg = Registry()
    lim = MetricsDecorator(forced_limiter, registry=reg)

    def scraped(name):
        lines = [ln for ln in reg.render().splitlines()
                 if ln.startswith(name)]
        assert len(lines) == 1
        return float(lines[0].split()[-1])

    assert scraped("rate_limiter_sketch_rows_total") == 0.0
    lim.allow_hashed(np.array([5, 9, 5, 9, 5, 7, 7, 7], np.uint64))
    assert scraped("rate_limiter_sketch_rows_total") == 8.0
    assert scraped("rate_limiter_sketch_access_runs_total") == 3.0


def test_the_bucket_and_the_mesh_ship_no_tail():
    """Only the windowed single-device serving step ships the counts;
    the limiters built on other steps expect no tail whatever the
    predicate says."""
    from ratelimiter_tpu.algorithms.sketch import SketchTokenBucketLimiter
    from ratelimiter_tpu.parallel.limiter import _MeshPlacement

    with forced(True):
        assert SketchTokenBucketLimiter._tail_words(None, 65536) == 0
        assert _MeshPlacement._tail_words(None, 65536) == 0


# ------------------------------------------------ the replicated mesh

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 (virtual) devices")


@needs_mesh
@pytest.mark.parametrize("merge", ["gather", "delta"])
def test_the_mesh_steps_carry_it_and_answer_as_before(monkeypatch, merge):
    """The replicated mesh runs the same body: gather mode on the whole
    gathered batch, delta mode on each chip's shard with the psum of the
    run-summed histograms. Same verdicts and the same replicated state
    as the per-row body; neither ships a tail."""
    import warnings

    from ratelimiter_tpu import ManualClock
    from ratelimiter_tpu.parallel import mesh_kernels
    from ratelimiter_tpu.parallel.limiter import MeshSketchLimiter
    from ratelimiter_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(3)
    ids = np.minimum(rng.zipf(1.1, 100), 1 << 18).astype(np.uint64)
    got = {}
    for dedup in (False, True):
        monkeypatch.setattr(sketch_kernels, "_BUILT", {})
        monkeypatch.setattr(mesh_kernels, "_BUILT", {})
        monkeypatch.setattr(sortmerge, "_use_run_dedup",
                            lambda B_, w_, on=dedup: on)
        monkeypatch.setattr(sortmerge, "run_chunk", lambda B_: CHUNK)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            lim = MeshSketchLimiter(_cfg(), ManualClock(1_700_000_000.0),
                                    mesh=make_mesh(n_devices=4), merge=merge)
        res = [lim.allow_hashed(ids), lim.allow_ids(ids)]
        got[dedup] = (res, {k: np.asarray(v) for k, v in lim._state.items()})
        assert lim.table_access_stats == {"rows": 0, "runs": 0}
        lim.close()
    for a, b in zip(got[False][0], got[True][0]):
        for col in ("allowed", "remaining", "retry_after", "reset_at"):
            np.testing.assert_array_equal(getattr(a, col), getattr(b, col))
    for leaf, v in got[False][1].items():
        np.testing.assert_array_equal(v, got[True][1][leaf], err_msg=leaf)
    assert got[False][0][0].allowed.any()
