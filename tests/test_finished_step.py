"""One transfer and one program launch per dispatch (ADR-010 addendum).

The serving steps take ONE staged uint64 buffer ``[ids(P) | n(P) |
now_us(1)]`` and end with the retry/reset arithmetic that used to be a
program of its own (``finish_window`` / ``finish_bucket`` behind
``_launch_finish``). Three things are held here:

* the finished step's four outputs equal the (h1, h2) step followed by
  the OLD finish arithmetic, bit for bit — the old programs are kept
  below as the plain reference, operands traced exactly as they were;
* a launch makes exactly one jitted call and one explicit placement and
  no implicit transfer;
* the mesh placement's own staging hook gives the single-chip limiter's
  four columns.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ratelimiter_tpu import Algorithm, Config, ManualClock, SketchParams
from ratelimiter_tpu.algorithms.sketch import (
    SketchLimiter,
    SketchTokenBucketLimiter,
)
from ratelimiter_tpu.core.clock import to_micros
from ratelimiter_tpu.ops import bucket_kernels, sketch_kernels
from ratelimiter_tpu.ops.hashing import split_hash, splitmix64

T0 = 1_700_000_000.25
ALGOS = {"windowed": Algorithm.SLIDING_WINDOW,
         "fixed": Algorithm.FIXED_WINDOW,
         "bucket": Algorithm.TOKEN_BUCKET}


def _cfg(algo: str, **kw) -> Config:
    base = dict(algorithm=ALGOS[algo], limit=3, window=60.0,
                sketch=SketchParams(depth=3, width=512, sub_windows=6))
    base.update(kw)
    return Config(**base)


# The programs this PR removed, as they were: jitted on their own, with
# now_us and window_us as traced int64 operands.

@jax.jit
def _old_finish_window(allowed, remaining, now_us, window_us):
    cur_ws = (now_us // window_us) * window_us
    reset = (cur_ws + window_us).astype(jnp.float64) / 1e6
    retry = jnp.where(allowed, jnp.float64(0.0),
                      (cur_ws + window_us - now_us).astype(jnp.float64) / 1e6)
    return (allowed, remaining.astype(jnp.int64), retry,
            jnp.broadcast_to(reset, allowed.shape))


@jax.jit
def _old_finish_bucket(allowed, remaining, retry_us, now_us, window_us):
    reset = (now_us + window_us).astype(jnp.float64) / 1e6
    return (allowed, remaining.astype(jnp.int64),
            retry_us.astype(jnp.float64) / 1e6,
            jnp.broadcast_to(reset, allowed.shape))


def _stage(ids: np.ndarray, ns: np.ndarray, now_us: int,
           padded: int) -> np.ndarray:
    b = ids.shape[0]
    buf = np.zeros(2 * padded + 1, dtype=np.uint64)
    buf[:b] = ids
    buf[padded:padded + b] = ns
    buf[2 * padded] = now_us
    return buf


@pytest.mark.parametrize("b,padded", [(8, 8), (4096, 4096), (1003, 1024)],
                         ids=["pad8", "pad4096", "ragged"])
@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo", list(ALGOS))
def test_finished_step_equals_step_then_old_finish(algo, premix, b, padded):
    cfg = _cfg(algo)
    window_us = to_micros(cfg.window)
    kernels = bucket_kernels if algo == "bucket" else sketch_kernels
    steps = kernels.build_steps(cfg)
    fused = kernels.build_hashed_step(cfg, premix=premix)
    ours, ref = kernels.init_state(cfg), kernels.init_state(cfg)

    rng = np.random.default_rng(b + 17 * premix)
    admitted = denied = 0
    period = None
    # Three dispatches on one state: few keys against limit 3, so each
    # holds admissions and denials, and the later ones meet counters the
    # earlier ones wrote. The third crosses into the next sub-window.
    for now_us in (to_micros(T0), to_micros(T0) + 1_234_567,
                   to_micros(T0) + 11_000_003):
        ids = rng.integers(1, max(2, b // 3), size=b).astype(np.uint64)
        ns = rng.integers(1, 3, size=b).astype(np.int64)
        if algo != "bucket" and period != (
                p := now_us // sketch_kernels.sketch_geometry(cfg)[1]):
            period = p                  # the host's _sync_period
            ours = steps[2](ours, np.int64(p))
            ref = steps[2](ref, np.int64(p))
        ours, got = fused(ours, _stage(ids, ns, now_us, padded))

        h1, h2 = split_hash(splitmix64(ids) if premix else ids,
                            cfg.sketch.seed)
        pad = lambda a, dt: np.concatenate(
            [a, np.zeros(padded - b, a.dtype)]).astype(dt)
        ref, (allowed, remaining, third) = steps[0](
            ref, pad(h1, np.uint32), pad(h2, np.uint32), pad(ns, np.int32),
            jnp.int64(now_us))
        if algo == "bucket":
            want = _old_finish_bucket(allowed, remaining, third,
                                      jnp.int64(now_us), jnp.int64(window_us))
        else:
            want = _old_finish_window(allowed, remaining, jnp.int64(now_us),
                                      jnp.int64(window_us))

        assert len(got) == 4
        for g, w_ in zip(got, want):
            g, w_ = np.asarray(g), np.asarray(w_)
            assert g.dtype == w_.dtype and g.shape == (padded,)
            # Bit for bit: floats compared as their 64-bit patterns.
            np.testing.assert_array_equal(g.view(np.uint8), w_.view(np.uint8))
        admitted += int(np.asarray(got[0])[:b].sum())
        denied += b - int(np.asarray(got[0])[:b].sum())
    assert admitted and denied
    for k in ours:
        np.testing.assert_array_equal(np.asarray(ours[k]), np.asarray(ref[k]))


def test_the_guard_is_live_on_this_backend():
    """The control of the next test: an implicit host->device transfer
    raises under the guard here, an explicit one does not."""
    double = jax.jit(lambda x: x * 2)
    with jax.transfer_guard("disallow"):
        double(jax.device_put(np.arange(4)))
        with pytest.raises(Exception, match="[Dd]isallowed"):
            double(np.arange(4))


@pytest.mark.parametrize("pinned", [False, True], ids=["default", "pinned"])
@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("algo", list(ALGOS))
def test_a_launch_is_one_transfer_and_one_program(algo, premix, pinned,
                                                  monkeypatch):
    cls = SketchTokenBucketLimiter if algo == "bucket" else SketchLimiter
    device = jax.devices()[-1] if pinned else None
    lim = cls(_cfg(algo), ManualClock(T0), device=device)
    launch = lim.launch_ids if premix else lim.launch_hashed
    ids = np.arange(1, 6, dtype=np.uint64)
    lim.resolve(launch(ids))           # compile, rotate, place the policy

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
        ticket = launch(ids)
    monkeypatch.undo()

    assert calls == {"step": 1, "put": 1}
    # The ticket carries the step's own outputs: nothing ran after it.
    assert ticket.outs is stepped[0] and len(ticket.outs) == 4
    assert ticket.slot.dtype == np.uint64
    assert ticket.slot.shape == (2 * ticket.padded + 1,)
    if pinned:
        assert all(o.devices() == {device} for o in ticket.outs)
    out = lim.resolve(ticket)
    assert out.retry_after.dtype == np.float64
    assert out.remaining.dtype == np.int64
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

