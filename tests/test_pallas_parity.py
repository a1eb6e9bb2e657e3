"""Fused Pallas kernels vs the jnp reference — BIT-IDENTICAL (ADR-011).

The ``kernels`` knob is an execution choice, not a semantic one: a
limiter built with ``kernels="pallas"`` (interpret mode on this CPU CI —
same numerics as a compiled TPU kernel) must produce exactly the same
decisions, remaining, retry and reset as ``kernels="jnp"``, decision for
decision, across sub-window rollovers, policy overrides, conservative
and vanilla updates, the token-bucket variant, and the lax.scan path.
Any drift here would make the knob silently re-shape admissions — these
tests are the contract that keeps ``kernels`` out of the checkpoint
fingerprint.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from ratelimiter_tpu import Algorithm, Config, SketchParams
from ratelimiter_tpu.algorithms.sketch import (
    SketchLimiter,
    SketchTokenBucketLimiter,
)
from ratelimiter_tpu.core.clock import ManualClock
from ratelimiter_tpu.core.errors import InvalidConfigError

T0 = 1_000_000.0


def _cfg(kernels: str, *, algo=Algorithm.SLIDING_WINDOW, cu=True,
         limit=7, hh=0) -> Config:
    return Config(
        algorithm=algo, limit=limit, window=6.0,
        sketch=SketchParams(depth=3, width=128, sub_windows=6,
                            conservative_update=cu, hh_slots=hh,
                            kernels=kernels))


def _pair(kernels_cfg: Config):
    cls = (SketchTokenBucketLimiter
           if kernels_cfg.algorithm is Algorithm.TOKEN_BUCKET
           else SketchLimiter)
    jnp_cfg = dataclasses.replace(
        kernels_cfg,
        sketch=dataclasses.replace(kernels_cfg.sketch, kernels="jnp"))
    return (cls(kernels_cfg, ManualClock(T0)), cls(jnp_cfg, ManualClock(T0)))


def _assert_same(a, b):
    np.testing.assert_array_equal(np.asarray(a.allowed),
                                  np.asarray(b.allowed))
    np.testing.assert_array_equal(np.asarray(a.remaining),
                                  np.asarray(b.remaining))
    np.testing.assert_array_equal(np.asarray(a.retry_after),
                                  np.asarray(b.retry_after))
    np.testing.assert_array_equal(np.asarray(a.reset_at),
                                  np.asarray(b.reset_at))


def _drive(lp, lj, *, steps=14, batch=48, n_keys=24, seed=0,
           advance=0.75):
    """Drive both limiters with the same Zipf-ish trace across several
    sub-window rollovers (sub-window = 1 s; advance 0.75 s/step crosses
    boundaries at the same virtual instants for both) and compare every
    field of every batch bit-exactly."""
    rng = np.random.default_rng(seed)
    for step in range(steps):
        ids = rng.integers(1, n_keys, size=batch).astype(np.uint64)
        ns = rng.integers(1, 3, size=batch).astype(np.int64)
        rp = lp.allow_ids(ids, ns)
        rj = lj.allow_ids(ids, ns)
        _assert_same(rp, rj)
        lp.clock.advance(advance)
        lj.clock.advance(advance)


@pytest.mark.parametrize("cu", [True, False])
@pytest.mark.parametrize("algo", [Algorithm.SLIDING_WINDOW,
                                  Algorithm.FIXED_WINDOW])
def test_windowed_parity_across_rollovers(algo, cu):
    lp, lj = _pair(_cfg("pallas", algo=algo, cu=cu))
    try:
        _drive(lp, lj)
    finally:
        lp.close()
        lj.close()


def test_token_bucket_parity():
    lp, lj = _pair(_cfg("pallas", algo=Algorithm.TOKEN_BUCKET))
    try:
        _drive(lp, lj, advance=0.4)
    finally:
        lp.close()
        lj.close()


def test_policy_override_parity():
    lp, lj = _pair(_cfg("pallas"))
    try:
        for lim in (lp, lj):
            lim.set_override("whale", 50)
            lim.set_override("guppy", 2)
        keys = (["whale"] * 20 + ["guppy"] * 6 + ["plain"] * 10) * 2
        for _ in range(6):
            rp = lp.allow_batch(keys)
            rj = lj.allow_batch(keys)
            _assert_same(rp, rj)
            if rp.limits is None:
                assert rj.limits is None
            else:
                np.testing.assert_array_equal(rp.limits, rj.limits)
            lp.clock.advance(0.9)
            lj.clock.advance(0.9)
    finally:
        lp.close()
        lj.close()


def test_scan_path_parity():
    """build_scan honors the kernels knob: a pallas-kernel scan equals
    the jnp-kernel scan bit for bit (packed masks AND final state)."""
    import jax.numpy as jnp

    from ratelimiter_tpu.ops import sketch_kernels as sk

    T0_US = 1_700_000_000 * 1_000_000
    cfgs = {k: Config(algorithm=Algorithm.SLIDING_WINDOW, limit=9,
                      window=6.0,
                      sketch=SketchParams(depth=3, width=64, sub_windows=6,
                                          kernels=k))
            for k in ("pallas", "jnp")}
    rng = np.random.default_rng(5)
    T, B = 4, 16
    h1 = rng.integers(0, 2 ** 32, size=(T, B), dtype=np.uint32)
    h2 = rng.integers(0, 2 ** 32, size=(T, B), dtype=np.uint32) | 1
    ns = np.ones((T, B), np.int32)
    outs = {}
    for k, cfg in cfgs.items():
        _, sub, _, _, _ = sk.sketch_geometry(cfg)
        _, _, roll = sk.build_steps(cfg)
        st = roll(sk.init_state(cfg), jnp.int64(T0_US // sub))
        scan = sk.build_scan(cfg)
        st, packed, denies = scan(st, jnp.asarray(h1), jnp.asarray(h2),
                                  jnp.asarray(ns), jnp.int64(T0_US),
                                  jnp.int64(1000))
        outs[k] = (np.asarray(packed), np.asarray(denies),
                   {kk: np.asarray(v) for kk, v in st.items()})
    np.testing.assert_array_equal(outs["pallas"][0], outs["jnp"][0])
    np.testing.assert_array_equal(outs["pallas"][1], outs["jnp"][1])
    for kk in outs["jnp"][2]:
        np.testing.assert_array_equal(outs["pallas"][2][kk],
                                      outs["jnp"][2][kk])


def test_reset_parity_after_mixed_traffic():
    lp, lj = _pair(_cfg("pallas"))
    try:
        keys = ["a"] * 6 + ["b"] * 3
        for lim in (lp, lj):
            lim.allow_batch(keys)
            lim.reset("a")
        rp = lp.allow_batch(keys)
        rj = lj.allow_batch(keys)
        _assert_same(rp, rj)
    finally:
        lp.close()
        lj.close()


def test_auto_resolves_jnp_off_tpu():
    from ratelimiter_tpu.ops import pallas_sketch

    cfg = _cfg("auto")
    assert pallas_sketch.resolve_kernels(cfg) == "jnp"  # CPU backend


def test_pallas_rejects_hh_side_table():
    from ratelimiter_tpu.ops import pallas_sketch

    cfg = _cfg("pallas", hh=64)
    with pytest.raises(InvalidConfigError):
        pallas_sketch.resolve_kernels(cfg)
    # auto with hh falls back silently (the side table is a supported
    # configuration; the fused kernels just don't cover it).
    assert pallas_sketch.resolve_kernels(_cfg("auto", hh=64)) == "jnp"


def test_kernels_knob_validated():
    with pytest.raises(InvalidConfigError):
        _cfg("mosaic").validate()
    _cfg("pallas").validate()
    _cfg("jnp").validate()


def test_kernels_knob_excluded_from_fingerprint():
    from ratelimiter_tpu.checkpoint import config_fingerprint

    assert (config_fingerprint(_cfg("pallas"))
            == config_fingerprint(_cfg("jnp"))
            == config_fingerprint(_cfg("auto")))


def test_mosaic_still_refuses_the_fused_kernels(monkeypatch):
    """Why resolve_kernels keeps every TPU off these kernels
    (pallas_sketch.MOSAIC_REFUSAL): lowered for a TPU with interpret
    mode off, the first of them is refused at trace time. When a JAX
    upgrade or a kernel rewrite makes this pass, the selection rule is
    due for review (ROADMAP S3/D5)."""
    import jax
    import jax.numpy as jnp

    from ratelimiter_tpu.ops import pallas_sketch

    monkeypatch.setattr(pallas_sketch, "_interpret", lambda: False)
    d, w, B = 4, 65536, 4096
    table = jax.ShapeDtypeStruct((d, w), jnp.int32)
    h = jax.ShapeDtypeStruct((B,), jnp.uint32)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        jax.jit(pallas_sketch.window_estimate).trace(
            table, table, jnp.float32(0.5), h, h).lower(
                lowering_platforms=("tpu",))
