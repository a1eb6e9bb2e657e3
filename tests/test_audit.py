"""Live accuracy observatory (ADR-016) — tier-1 suite.

Covers, per ISSUE 9:

* the shared three-way engine: Wilson intervals, tally arithmetic, the
  inlined windowed host oracle fuzz-pinned IDENTICAL to ExactLimiter
  (the exact==dense parity chain then reaches the device oracle), and
  the CMS-vs-semantic split on a deliberately colliding sketch;
* the auditor core: hash-coherent sampling (a key is always or never
  audited, across lanes), per-slice attribution, fail-open exclusion
  (degraded ranges attributed, not averaged away), drop-and-count under
  a full queue, shadow failures contained;
* audit-off = byte-identical hot path (pinned on the asyncio door), and
  audit-ON decisions also byte-identical (the tap is passive);
* both doors' taps end to end: the auditor's tally equals an offline
  recomputation of the same decisions at sample=1;
* chaos integration: a quarantined slice's fail-open rows are counted
  per slice and never pollute the accuracy rates;
* the SLO burn-rate tracker (windows, axes, gauges, fallback source);
* top-K consumer analytics off the hh side table (limiter surface,
  MetricsDecorator gauges, /healthz merge);
* LoggingDecorator satellites (key redaction, fail_open_slices);
* GET /debug/audit trust boundary and the combined /healthz envelope
  with mesh + quarantine + audit all enabled (the composition no test
  exercised before);
* the live estimate against ``evaluation.evaluate_accuracy`` on one
  stream (the offline rate inside the live Wilson interval).
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from ratelimiter_tpu import (
    Algorithm,
    Config,
    ManualClock,
    SketchParams,
    create_limiter,
)
from ratelimiter_tpu.algorithms.exact import ExactLimiter
from ratelimiter_tpu.core.types import BatchResult
from ratelimiter_tpu.evaluation.compare import (
    ShadowComparator,
    ThreeWayTally,
    wilson_interval,
)
from ratelimiter_tpu.observability import audit
from ratelimiter_tpu.observability import metrics as m
from ratelimiter_tpu.observability.decorators import (
    LoggingDecorator,
    MetricsDecorator,
)
from ratelimiter_tpu.observability.slo import SloBurnTracker
from ratelimiter_tpu.ops.hashing import splitmix64
from ratelimiter_tpu.serving.batcher import MicroBatcher
from ratelimiter_tpu.serving.client import AsyncClient, Client
from ratelimiter_tpu.serving.http_gateway import HttpGateway
from ratelimiter_tpu.serving.native_server import (
    NativeRateLimitServer,
    native_server_available,
)
from ratelimiter_tpu.serving.server import RateLimitServer

T0 = 1_700_000_000.0


@pytest.fixture(autouse=True)
def _audit_off():
    """Every test starts and ends with the module seam clear — the
    zero-overhead default the rest of the suite relies on."""
    audit.disable()
    yield
    audit.disable()


def _cfg(limit=100, width=1 << 12, depth=2, sub_windows=8, **kw):
    return Config(algorithm=Algorithm.SLIDING_WINDOW, limit=limit,
                  window=60.0, key_prefix="",
                  sketch=SketchParams(depth=depth, width=width,
                                      sub_windows=sub_windows), **kw)


def _batch_result(allowed, *, fail_open=False, limit=100):
    allowed = np.asarray(allowed, dtype=bool)
    b = allowed.shape[0]
    return BatchResult(allowed=allowed, limit=limit,
                       remaining=np.zeros(b, np.int64),
                       retry_after=np.zeros(b, np.float64),
                       reset_at=np.zeros(b, np.float64),
                       fail_open=fail_open)


# ------------------------------------------------------------ the engine


class TestWilson:
    def test_contains_point_estimate(self):
        for k, n in [(0, 10), (1, 100), (50, 100), (99, 100)]:
            lo, hi = wilson_interval(k, n)
            assert lo <= k / n <= hi

    def test_no_evidence(self):
        assert wilson_interval(0, 0) == (0.0, 1.0)

    def test_clamped_and_ordered(self):
        for k, n in [(0, 5), (5, 5), (3, 7)]:
            lo, hi = wilson_interval(k, n)
            assert 0.0 <= lo <= hi <= 1.0

    def test_narrows_with_n(self):
        lo1, hi1 = wilson_interval(1, 100)
        lo2, hi2 = wilson_interval(100, 10_000)
        assert (hi2 - lo2) < (hi1 - lo1)


class TestTally:
    def test_counts(self):
        t = ThreeWayTally()
        live = np.array([True, False, False, True])
        twin = np.array([True, True, False, True])
        oracle = np.array([True, True, True, False])
        t.add(live, twin, oracle)
        assert t.requests == 4
        assert t.oracle_allows == 3
        assert t.false_denies_vs_oracle == 2   # idx 1, 2
        assert t.false_allows_vs_oracle == 1   # idx 3
        assert t.cms_false_denies_vs_twin == 1  # idx 1
        assert t.semantic_disagreements == 2   # idx 2, 3
        assert t.false_deny_rate == 2 / 3

    def test_twinless(self):
        t = ThreeWayTally()
        t.add(np.array([True]), None, np.array([False]))
        assert t.false_allows_vs_oracle == 1
        assert t.cms_false_denies_vs_twin == 0


class TestOracleParity:
    """The inlined windowed oracle must be bit-identical to ExactLimiter
    (which is itself pinned bit-identical to the dense device oracle by
    tests/test_cross_backend.py)."""

    @pytest.mark.parametrize("algo", [Algorithm.SLIDING_WINDOW,
                                      Algorithm.FIXED_WINDOW])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_fuzz_vs_exact(self, algo, seed):
        cfg = Config(algorithm=algo, limit=7, window=3.0, key_prefix="",
                     sketch=SketchParams(depth=1, width=1 << 14,
                                         sub_windows=6))
        comp = ShadowComparator(cfg, include_twin=False)
        ex = ExactLimiter(Config(algorithm=algo, limit=7, window=3.0,
                                 key_prefix=""))
        rng = np.random.default_rng(seed)
        t = T0
        try:
            for _ in range(100):
                b = int(rng.integers(1, 24))
                h = rng.integers(1, 40, size=b).astype(np.uint64)
                ns = rng.integers(1, 3, size=b).astype(np.int64)
                # Includes idle gaps > window (both-expired resets) and
                # sub-window steps (weighted boundary math).
                t += float(rng.random() * 1.7)
                fast, _ = comp.decide(h, ns, t)
                exp = ex.allow_batch([f"k{int(x)}" for x in h],
                                     [int(n) for n in ns], now=t).allowed
                assert np.array_equal(fast, exp)
        finally:
            comp.close()
            ex.close()

    def test_prune_preserves_semantics(self):
        """Sweeping fully-stale entries is invisible: a key idle past
        one window decides identically whether its entry was pruned or
        kept."""
        cfg = _cfg(limit=3)
        comp = ShadowComparator(cfg, include_twin=False,
                                oracle_capacity=1024)
        h = np.array([42], dtype=np.uint64)
        comp.decide(h, np.array([3]), T0)       # key at its limit
        denied, _ = comp.decide(h, np.array([1]), T0 + 1.0)
        assert not denied[0]
        # Force the sweep: flood with > 4*cap distinct fresh keys two
        # windows later, then the idle key must decide as fresh.
        later = T0 + 200.0
        comp.decide(np.arange(1000, 6000, dtype=np.uint64),
                    None, later)
        assert len(comp._sw_state) < 6000 + 2   # stale swept
        fresh, _ = comp.decide(h, np.array([1]), later)
        assert fresh[0]
        comp.close()

    def test_cms_split_on_colliding_sketch(self):
        """A deliberately tiny sketch produces false denies that the
        collision-free twin attributes to CMS error, not semantics."""
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=20,
                     window=60.0, key_prefix="",
                     sketch=SketchParams(depth=1, width=64,
                                         sub_windows=6))
        from ratelimiter_tpu.algorithms.sketch import SketchLimiter

        lim = SketchLimiter(cfg, ManualClock(T0))
        comp = ShadowComparator(cfg, include_twin=True,
                                twin_width=1 << 16)
        rng = np.random.default_rng(0)
        h = splitmix64(rng.integers(0, 2000, size=6000,
                                    dtype=np.uint64))
        for i in range(0, 6000, 512):
            now = T0 + i / 2000.0
            live = lim.allow_hashed(h[i:i + 512], now=now).allowed
            comp.observe(h[i:i + 512], None, now, live)
        t = comp.tally
        assert t.false_denies_vs_oracle > 0
        # The split attributes (nearly) all of it to collisions.
        assert t.cms_false_denies_vs_twin > 0
        assert t.cms_false_denies_vs_twin >= t.false_denies_vs_oracle / 2
        lim.close()
        comp.close()


# ------------------------------------------------------------ the auditor


class TestAuditorCore:
    def make(self, **kw):
        kw.setdefault("start", False)
        kw.setdefault("include_twin", False)
        return audit.ShadowAuditor(_cfg(), **kw)

    def test_hash_coherent_sampling(self):
        """A key is ALWAYS or NEVER audited: two frames containing the
        same keys contribute the same audited subset, and it matches
        the documented rule."""
        aud = self.make(sample=8)
        h = np.arange(1, 513, dtype=np.uint64) * np.uint64(0x9E3779B9)
        res = _batch_result(np.ones(512, bool))
        aud.offer_hashed(h, None, T0, res)
        aud.process_pending()
        first = aud.status()["samples"]
        expected = int(((h >> np.uint64(61)) == 0).sum())
        assert first == expected > 0
        aud.offer_hashed(h, None, T0 + 1.0, res)
        aud.process_pending()
        assert aud.status()["samples"] == 2 * first
        aud.close()

    def test_lane_coherence_ids_vs_hashed(self):
        """The raw-id lane finalizes with splitmix64 before sampling —
        the same subset as a pre-finalized offer of splitmix64(ids)."""
        aud = self.make(sample=4)
        ids = np.arange(100, 400, dtype=np.uint64)
        res = _batch_result(np.ones(300, bool))
        aud.offer_ids(ids, None, T0, res)
        aud.process_pending()
        via_ids = aud.status()["samples"]
        aud2 = self.make(sample=4)
        aud2.offer_hashed(splitmix64(ids), None, T0, res)
        aud2.process_pending()
        assert aud2.status()["samples"] == via_ids > 0
        aud.close()
        aud2.close()

    def test_string_lane_applies_prefix(self):
        """offer_keys hashes with the limiter's prefix rule, so the
        audited decisions line up with what the backend decided."""
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5,
                     window=60.0, key_prefix="rl",
                     sketch=SketchParams(depth=2, width=1 << 12,
                                         sub_windows=8))
        aud = audit.ShadowAuditor(cfg, sample=1, start=False,
                                  include_twin=False)
        lim = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
        keys = [f"user:{i}" for i in range(32)]
        out = lim.allow_batch(keys, now=T0)
        aud.offer_keys(keys, None, T0, out)
        aud.process_pending()
        st = aud.status()
        assert st["samples"] == 32
        assert st["false_denies"] == 0 and st["false_allows"] == 0
        lim.close()
        aud.close()

    def test_per_slice_attribution(self):
        """Mismatches land on the slice the key routes to
        (h64 % n_slices — the SlicedMeshLimiter router)."""
        aud = self.make(sample=1, n_slices=4)
        h = np.arange(1, 65, dtype=np.uint64)
        # Live DENIES everything; the oracle allows (fresh keys) — 64
        # false denies attributed per slice.
        res = _batch_result(np.zeros(64, bool))
        aud.offer_hashed(h, None, T0, res)
        aud.process_pending()
        st = aud.status()
        assert st["false_denies"] == 64
        per = st["per_slice"]
        assert set(per) == {"0", "1", "2", "3"}
        for s, d in per.items():
            exp = int((h % np.uint64(4) == np.uint64(int(s))).sum())
            assert d["samples"] == exp
            assert d["false_denies"] == exp
        aud.close()

    def test_fail_open_attributed_not_averaged(self):
        """Fail-open rows are excluded from the rates and counted on
        the named slices only; un-named slices' rows still compare."""
        aud = self.make(sample=1, n_slices=4)
        h = np.arange(1, 65, dtype=np.uint64)
        res = _batch_result(np.ones(64, bool), fail_open=True)
        res.fail_open_slices = [1]
        aud.offer_hashed(h, None, T0, res)
        aud.process_pending()
        st = aud.status()
        on_victim = int((h % np.uint64(4) == np.uint64(1)).sum())
        assert st["fail_open_samples"] == on_victim
        assert st["per_slice"]["1"]["fail_open_samples"] == on_victim
        assert st["per_slice"]["1"]["samples"] == 0
        # Healthy slices' rows were compared normally (fresh keys,
        # allowed == oracle) — no false counts anywhere.
        assert st["samples"] == 64 - on_victim
        assert st["false_denies"] == 0 and st["false_allows"] == 0
        aud.close()

    def test_unattributed_fail_open_excludes_frame(self):
        aud = self.make(sample=1, n_slices=2)
        res = _batch_result(np.ones(16, bool), fail_open=True)
        aud.offer_hashed(np.arange(1, 17, dtype=np.uint64), None, T0, res)
        aud.process_pending()
        st = aud.status()
        assert st["fail_open_samples"] == 16
        assert st["samples"] == 0
        aud.close()

    def test_drop_and_count_never_blocks(self):
        aud = self.make(sample=1, queue_depth=2)
        res = _batch_result(np.ones(8, bool))
        for _ in range(10):
            aud.offer_hashed(np.arange(8, dtype=np.uint64), None, T0, res)
        assert aud.dropped_frames == 8
        assert aud.dropped_decisions == 64
        assert len(aud._q) == 2
        aud.process_pending()
        assert aud.status()["dropped_decisions"] == 64
        aud.close()

    def test_shadow_failure_contained(self, monkeypatch):
        """A shadow-leg crash is counted and dropped — it must never
        propagate toward serving."""
        aud = self.make(sample=1)
        monkeypatch.setattr(aud._comparator, "decide",
                            lambda *a, **k: 1 / 0)
        aud.offer_hashed(np.arange(4, dtype=np.uint64), None, T0,
                         _batch_result(np.ones(4, bool)))
        aud.process_pending()   # must not raise
        assert aud.oracle_errors == 1
        assert aud.status()["samples"] == 0
        aud.close()

    def test_live_config_update_rebaselines_shadow(self):
        """A runtime update_limit on the audited backend must not turn
        every allow between the old and new limit into a permanent
        false-allow reading: the worker follows live_config and
        re-baselines the shadow legs."""
        cfg = _cfg(limit=5)
        lim = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
        aud = audit.ShadowAuditor(cfg, sample=1, start=False,
                                  include_twin=False,
                                  live_config=lambda: lim.config)
        h = np.full(12, 77, dtype=np.uint64)
        out = lim.allow_hashed(h, now=T0)        # 5 allowed, 7 denied
        aud.offer_hashed(h, None, T0, out)
        aud.process_pending()
        assert aud.status()["false_allows"] == 0
        lim.update_limit(12)
        out2 = lim.allow_hashed(h, now=T0 + 1.0)  # 7 more allowed
        assert int(out2.allowed.sum()) == 7
        aud.offer_hashed(h, None, T0 + 1.0, out2)
        aud.process_pending()
        st = aud.status()
        # Without the re-baseline the oracle (still at limit 5) would
        # score those 7 allows as false allows.
        assert st["false_allows"] == 0
        assert st["false_denies"] == 0
        aud.close()
        lim.close()

    def test_scalar_result_normalized(self):
        """decide_one-style taps carry a scalar Result."""
        from ratelimiter_tpu.core.types import allowed_result

        aud = self.make(sample=1)
        aud.offer_keys(["k"], [1], T0, allowed_result(10, 9, T0 + 60))
        aud.process_pending()
        assert aud.status()["samples"] == 1
        aud.close()

    def test_registry_gauges(self):
        reg = m.Registry()
        aud = audit.ShadowAuditor(_cfg(), sample=1, n_slices=2,
                                  start=False, include_twin=False,
                                  registry=reg)
        res = _batch_result(np.zeros(8, bool))   # all false denies
        aud.offer_hashed(np.arange(1, 9, dtype=np.uint64), None, T0, res)
        aud.process_pending()
        text = reg.render()
        assert "rate_limiter_audit_false_deny_rate 1" in text
        assert "rate_limiter_audit_samples 8" in text
        assert 'rate_limiter_audit_slice_false_denies{slice="0"}' in text
        aud.close()
        # close() unhooks: a later render must not poke the auditor.
        reg.render()

    def test_enable_disable_seam(self):
        assert audit.AUDITOR is None
        a = audit.enable(_cfg(), sample=4, include_twin=False)
        assert audit.get() is a
        audit.disable()
        assert audit.AUDITOR is None


# ------------------------------------------- hot path + asyncio door tap


class TestAsyncioDoor:
    def _drive(self, *, enable_audit: bool, sample: int = 1):
        """One seeded trace through the real asyncio door; returns
        (decisions, audit status or None)."""
        cfg = _cfg(limit=5, width=1 << 11)

        async def run():
            clock = ManualClock(T0)
            lim = create_limiter(cfg, backend="sketch", clock=clock)
            srv = RateLimitServer(lim, max_batch=256, max_delay=50e-6)
            await srv.start()
            auditor = None
            if enable_audit:
                auditor = audit.enable(cfg, sample=sample, n_slices=1,
                                       include_twin=False)
            c = await AsyncClient.connect(srv.host, srv.port)
            rng = np.random.default_rng(0)
            ids = rng.integers(0, 200, size=1024).astype(np.uint64)
            allowed = []
            for i in range(0, 1024, 256):
                clock.set(T0 + i / 500.0)
                out = await c.allow_hashed(ids[i:i + 256])
                allowed.append(np.asarray(out.allowed))
            # String lane too (the client returns per-request Results).
            out = await c.allow_batch([f"u{i}" for i in range(64)])
            allowed.append(np.array([r.allowed for r in out]))
            await c.close()
            await srv.shutdown()
            lim.close()
            st = None
            if auditor is not None:
                assert auditor.flush(timeout=20)
                st = auditor.status()
                audit.disable()
            return np.concatenate(allowed), st

        return asyncio.run(run())

    def test_audit_off_is_default_and_byte_identical(self):
        assert audit.AUDITOR is None
        base, st = self._drive(enable_audit=False)
        assert st is None
        on, st_on = self._drive(enable_audit=True)
        # The tap is passive: decisions byte-identical with audit on.
        assert np.array_equal(base, on)
        assert st_on["samples"] > 0

    def test_tally_matches_offline_recomputation(self):
        """sample=1: the auditor's tally equals recomputing the same
        decisions offline against a fresh engine — the door tap loses
        nothing and invents nothing."""
        cfg = _cfg(limit=5, width=1 << 11)

        async def run():
            clock = ManualClock(T0)
            lim = create_limiter(cfg, backend="sketch", clock=clock)
            srv = RateLimitServer(lim, max_batch=256, max_delay=50e-6)
            await srv.start()
            auditor = audit.enable(cfg, sample=1, include_twin=False)
            c = await AsyncClient.connect(srv.host, srv.port)
            rng = np.random.default_rng(1)
            ids = rng.integers(0, 64, size=1024).astype(np.uint64)
            frames = []
            for i in range(0, 1024, 256):
                now = T0 + i / 400.0
                clock.set(now)
                out = await c.allow_hashed(ids[i:i + 256])
                frames.append((ids[i:i + 256], now,
                               np.asarray(out.allowed)))
            await c.close()
            await srv.shutdown()
            lim.close()
            assert auditor.flush(timeout=20)
            st = auditor.status()
            audit.disable()
            return frames, st

        frames, st = asyncio.run(run())
        comp = ShadowComparator(cfg, include_twin=False)
        for ids, now, allowed in frames:
            comp.observe(splitmix64(ids), None, now, allowed)
        t = comp.tally
        comp.close()
        assert st["samples"] == t.requests
        assert st["false_denies"] == t.false_denies_vs_oracle
        assert st["false_allows"] == t.false_allows_vs_oracle
        assert st["oracle_allows"] == t.oracle_allows
        # The tight trace over 64 hot keys at limit=5 actually denies —
        # the comparison above is not vacuous.
        assert t.oracle_allows < t.requests

    def test_slo_breach_frames_late_tapped(self):
        """A frame answered by SLO-breach policy still CONSUMES sketch
        mass via the shielded dispatch — its eventual device result is
        mirrored into the tap, so audited keys' shadow timelines have
        no holes (which would read as false denies later)."""
        import time as _time

        cfg = _cfg(limit=100, fail_open=True)

        async def run():
            lim = create_limiter(cfg, backend="sketch",
                                 clock=ManualClock(T0))
            real_allow = lim.allow_ids

            def slow_allow(ids, ns=None, *, now=None):
                _time.sleep(0.15)       # past the 50 ms SLO
                return real_allow(ids, ns, now=now)

            lim.allow_ids = slow_allow
            b = MicroBatcher(lim, max_batch=64, max_delay=1e-4,
                             dispatch_timeout=0.05)
            auditor = audit.enable(cfg, sample=1, include_twin=False)
            fut = b.submit_hashed_nowait(
                np.arange(8, dtype=np.uint64), np.ones(8, np.int64))
            out = await fut
            assert out.fail_open          # answered by breach policy
            await b.drain()
            b.close()                     # joins the executor: the
            #                               shielded call has landed
            await asyncio.sleep(0.05)     # let its done-callback run
            lim.close()
            assert auditor.flush(timeout=10)
            st = auditor.status()
            audit.disable()
            return st

        st = asyncio.run(run())
        # The REAL device decisions (not the fabricated fail-open
        # answers) reached the shadow oracle.
        assert st["samples"] == 8
        assert st["fail_open_samples"] == 0
        assert st["false_denies"] == 0 and st["false_allows"] == 0

    def test_batcher_tap_without_server(self):
        """The MicroBatcher itself taps (both lanes) — pinned without
        the socket layer."""
        cfg = _cfg(limit=100)

        async def run():
            lim = create_limiter(cfg, backend="sketch",
                                 clock=ManualClock(T0))
            b = MicroBatcher(lim, max_batch=64, max_delay=1e-4)
            auditor = audit.enable(cfg, sample=1, include_twin=False)
            await b.submit("alice", 1)
            fut = b.submit_hashed_nowait(
                np.arange(8, dtype=np.uint64), np.ones(8, np.int64))
            await fut
            await b.drain()
            b.close()
            lim.close()
            assert auditor.flush(timeout=10)
            st = auditor.status()
            audit.disable()
            return st

        st = asyncio.run(run())
        assert st["samples"] == 9
        assert st["audited_frames"] == 2


# --------------------------------------------------------- native door


@pytest.mark.skipif(not native_server_available(),
                    reason="native server extension unavailable (no g++)")
class TestNativeDoor:
    def test_pipelined_hashed_tap(self):
        cfg = _cfg(limit=1000, width=1 << 12)
        lim = create_limiter(cfg, backend="sketch")
        srv = NativeRateLimitServer(lim, max_batch=512, inflight=4)
        auditor = audit.enable(cfg, sample=1, include_twin=False)
        try:
            srv.start()
            c = Client(port=srv.port)
            ids = np.arange(1, 65, dtype=np.uint64)
            out = c.allow_hashed(ids)
            assert len(out.allowed) == 64
            # String lane through the same door.
            c.allow_batch([f"u{i}" for i in range(32)])
            c.close()
            assert auditor.flush(timeout=20)
            st = auditor.status()
            assert st["samples"] == 64 + 32
            assert st["false_denies"] == 0 and st["false_allows"] == 0
            # Native taps attribute by dispatch shard.
            assert set(st["per_slice"]) == {"0"}
        finally:
            audit.disable()
            srv.shutdown()
            lim.close()

    def test_decide_one_tap(self):
        cfg = _cfg(limit=10)
        lim = create_limiter(cfg, backend="sketch")
        srv = NativeRateLimitServer(lim, max_batch=64, inflight=1)
        auditor = audit.enable(cfg, sample=1, include_twin=False)
        try:
            srv.start()
            res = srv.decide_one("gateway-user", 1)
            assert res.allowed
            assert auditor.flush(timeout=10)
            assert auditor.status()["samples"] == 1
        finally:
            audit.disable()
            srv.shutdown()
            lim.close()


# ------------------------------------------------------ chaos integration


class TestChaosIntegration:
    def test_quarantined_slice_attributed(self):
        """With a slice killed under chaos, its fail-open rows land in
        fail_open_samples on THAT slice; healthy ranges' accuracy stays
        clean — degraded ranges attributed, not averaged away."""
        jax = pytest.importorskip("jax")
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        from ratelimiter_tpu import MeshSpec, chaos as chaos_pkg
        from ratelimiter_tpu.parallel.limiter import SlicedMeshLimiter

        victim = 1
        cfg = Config(
            algorithm=Algorithm.SLIDING_WINDOW, limit=1000, window=60.0,
            fail_open=True, key_prefix="",
            sketch=SketchParams(depth=2, width=1 << 12, sub_windows=4),
            mesh=MeshSpec(devices=2, quarantine=True,
                          slice_deadline=0.2, probe_interval=30.0))
        lim = SlicedMeshLimiter(cfg)
        aud = audit.ShadowAuditor(cfg, sample=1, n_slices=2, start=False,
                                  include_twin=False)
        ids = np.arange(1024, dtype=np.uint64)
        lim.allow_ids(ids)          # warm every slice + guard warm gates
        inj = chaos_pkg.install(seed=7)
        try:
            inj.fail_slice(victim)
            now = lim.clock.now()
            for _ in range(3):
                out = lim.allow_ids(ids)
                aud.offer_ids(ids, None, now, out)
            aud.process_pending()
            st = aud.status()
            owners = lim.owner_of_id(ids)
            per_fault = int((owners == victim).sum())
            assert st["fail_open_samples"] == 3 * per_fault
            assert st["per_slice"][str(victim)]["fail_open_samples"] == \
                3 * per_fault
            # The healthy slice was compared normally and stayed clean
            # (limit is high; no real denies in this trace).
            assert st["false_denies"] == 0
            assert st["false_allows"] == 0
            assert st["per_slice"]["0"]["fail_open_samples"] == 0
            assert st["per_slice"]["0"]["samples"] == 3 * int(
                (owners == 0).sum())
        finally:
            chaos_pkg.uninstall()
            aud.close()
            lim.close()


# ------------------------------------------------------------- SLO burn


class _FakeTime:
    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        return self.t


class TestSloBurnTracker:
    def test_burn_rate_windows(self, monkeypatch):
        reg = m.Registry()
        fake = _FakeTime()
        monkeypatch.setattr("ratelimiter_tpu.observability.slo.time", fake)
        hist = reg.histogram("rate_limiter_stage_seconds")
        shed = reg.counter("rate_limiter_server_deadline_shed_total")
        req = reg.counter("rate_limiter_requests_total")
        tr = SloBurnTracker(reg, objective=0.99, latency_target=0.01,
                            stage="device", windows=(60.0,))
        tr.sample()                            # zero baseline
        fake.t += 61.0
        for _ in range(99):                    # the window's traffic
            hist.observe(0.001, stage="device")
            req.inc(result="allowed")
        hist.observe(0.5, stage="device")      # one slow span
        req.inc(result="allowed")
        shed.inc(1)                            # one shed decision
        st = tr.status()
        row = st["windows"]["60s"]
        # latency axis: 1 slow / 100 spans this window = 1% bad = burn
        # 1.0 at a 1% budget; availability: 1 shed / 101 ~= 0.99%.
        assert row["latency_bad_fraction"] == pytest.approx(0.01)
        assert row["availability_bad_fraction"] == pytest.approx(1 / 101,
                                                                 abs=1e-4)
        assert row["burn_rate"] == pytest.approx(1.0, abs=0.05)
        assert row["span_s"] == pytest.approx(61.0)
        assert st["latency_target_effective_s"] <= 0.01

    def test_slo_breach_counts_decisions_not_frames(self, monkeypatch):
        """One breached frame fails-open a WHOLE batch: the availability
        axis consumes the decision-unit breach counter, so a full
        latency outage burns ~1.0, not ~1/batch_size."""
        reg = m.Registry()
        fake = _FakeTime()
        monkeypatch.setattr("ratelimiter_tpu.observability.slo.time", fake)
        breach_dec = reg.counter(
            "rate_limiter_server_slo_breach_decisions_total")
        tr = SloBurnTracker(reg, objective=0.99, windows=(60.0,))
        tr.sample()
        fake.t += 61.0
        breach_dec.inc(4096)     # one breached 4096-decision frame
        st = tr.status()
        assert st["windows"]["60s"]["availability_bad_fraction"] == 1.0

    def test_fallback_to_dispatch_histogram(self):
        reg = m.Registry()
        disp = reg.histogram("rate_limiter_server_dispatch_seconds")
        disp.observe(0.2)
        tr = SloBurnTracker(reg, latency_target=0.05)
        st = tr.status()
        assert st["spans_observed"] == 1

    def test_gauges_on_collect(self, monkeypatch):
        reg = m.Registry()
        fake = _FakeTime()
        monkeypatch.setattr("ratelimiter_tpu.observability.slo.time", fake)
        hist = reg.histogram("rate_limiter_stage_seconds")
        tr = SloBurnTracker(reg, windows=(30.0,))
        tr.attach()
        hist.observe(1.0, stage="device")
        fake.t += 31.0
        text = reg.render()
        assert "rate_limiter_slo_burn_rate" in text
        tr.detach()

    def test_bad_objective_rejected(self):
        with pytest.raises(ValueError):
            SloBurnTracker(m.Registry(), objective=1.0)


# -------------------------------------------------------- top consumers


class TestTopConsumers:
    def _hot_limiter(self):
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=1000,
                     window=60.0, key_prefix="", max_batch_admission_iters=4,
                     sketch=SketchParams(depth=2, width=256, sub_windows=6,
                                         hh_slots=16,
                                         hh_promote_fraction=0.01))
        clock = ManualClock(T0)
        return create_limiter(cfg, backend="sketch", clock=clock), clock

    def test_consumer_stats_ordering(self):
        lim, _ = self._hot_limiter()
        for _ in range(40):
            lim.allow("whale")
        for _ in range(25):
            lim.allow("dolphin")
        st = lim.consumer_stats(k=5)
        assert st["slots"] == 16
        assert st["occupied"] >= 2
        top = st["top"]
        assert len(top) >= 2
        # The side table counts a promoted key's traffic from its claim
        # point (promotion threshold = 1% of limit = 10 here), so the
        # whale tracks ~30 of its 40 requests and stays ranked first.
        assert top[0]["in_window"] > top[1]["in_window"] > 0
        assert top[0]["in_window"] >= 25
        assert top[0]["share"] > top[1]["share"]
        # Identities are hash tokens, never raw keys.
        assert all(len(r["consumer"]) == 16 for r in top)
        lim.close()

    def test_no_hh_table(self):
        lim = create_limiter(_cfg(), backend="sketch",
                             clock=ManualClock(T0))
        assert lim.consumer_stats() == {"slots": 0, "occupied": 0,
                                        "top": []}
        assert lim.has_hh is False
        lim.close()

    def test_metrics_decorator_exports_topk(self):
        lim, clock = self._hot_limiter()
        reg = m.Registry()
        dec = MetricsDecorator(lim, reg)
        for _ in range(30):
            dec.allow("whale")
        text = reg.render()
        assert 'rate_limiter_top_consumer_mass{rank="1"' in text
        assert "rate_limiter_hh_tracked_consumers" in text
        gauge = reg.get("rate_limiter_top_consumer_mass")
        assert gauge.value(rank="1", shard="0", slice="0") > 0
        # Vacated ranks drop to 0 on the next scrape — no phantom
        # heavy hitters frozen at their last mass.
        assert gauge.value(rank="5", shard="0", slice="0") == 0.0
        clock.advance(120.0)               # whole window rolls off
        dec.allow("minnow")                # advance the sketch's period
        text = reg.render()
        assert gauge.value(rank="1", shard="0", slice="0") == 0.0
        dec.close()

    def test_healthz_merge(self):
        from ratelimiter_tpu.serving.__main__ import _consumers_health

        lim, _ = self._hot_limiter()
        for _ in range(30):
            lim.allow("whale")
        block = _consumers_health([lim])
        assert block["consumers"]["occupied"] >= 1
        # Counted from the promotion point (threshold 10 of 30 allows).
        assert block["consumers"]["top"][0]["in_window"] >= 15
        assert "slice" in block["consumers"]["top"][0]
        lim.close()
        # No hh table -> no block at all (healthz stays lean).
        lim2 = create_limiter(_cfg(), backend="sketch")
        assert _consumers_health([lim2]) == {}
        lim2.close()


# --------------------------------------------------- logging satellites


class TestLoggingSatellites:
    def _limiter(self, **kw):
        return create_limiter(_cfg(limit=5), backend="exact",
                              clock=ManualClock(T0), **kw)

    def test_redact_keys(self, caplog):
        lim = LoggingDecorator(self._limiter(), redact_keys=True)
        with caplog.at_level(logging.DEBUG, logger="ratelimiter_tpu"):
            lim.allow("alice@example.com")
            lim.reset("alice@example.com")
        text = "\n".join(r.message for r in caplog.records)
        assert "alice@example.com" not in text
        assert "key#" in text
        # Stable: the same key always logs the same token.
        tokens = {w for w in text.split() if w.startswith("key=key#")}
        assert len(tokens) == 1
        lim.close()

    def test_raw_keys_by_default(self, caplog):
        lim = LoggingDecorator(self._limiter())
        with caplog.at_level(logging.DEBUG, logger="ratelimiter_tpu"):
            lim.allow("bob")
        assert any("key=bob" in r.message for r in caplog.records)
        lim.close()

    def test_fail_open_names_slices(self, caplog):
        """A slice-attributed fail-open WARNING carries the slice list
        so the degraded-range line is actionable."""
        inner = self._limiter()

        class _Inner(LoggingDecorator):
            pass

        dec = LoggingDecorator(inner)
        out = _batch_result(np.ones(4, bool), fail_open=True)
        out.fail_open_slices = [2, 0]
        with caplog.at_level(logging.WARNING, logger="ratelimiter_tpu"):
            dec._observe_batch("allow_batch", out, None, 0.001)
        msg = caplog.records[-1].message
        assert "fail-open" in msg and "fail_open_slices=[0, 2]" in msg
        dec.close()

    def test_scalar_fail_open_names_slices(self, caplog):
        from ratelimiter_tpu.core.types import fail_open_result

        class FailOpenInner:
            config = _cfg(fail_open=True)

            def allow_n(self, key, n, *, now=None):
                res = fail_open_result(10, T0 + 60)
                object.__setattr__(res, "fail_open_slices", [3])
                return res

            def close(self):
                pass

        inner = create_limiter(_cfg(fail_open=True), backend="exact")
        dec = LoggingDecorator(inner, redact_keys=True)
        dec.inner = FailOpenInner()
        with caplog.at_level(logging.WARNING, logger="ratelimiter_tpu"):
            dec.allow_n("whale", 1)
        msg = caplog.records[-1].message
        assert "fail_open_slices=[3]" in msg and "whale" not in msg
        inner.close()


# ------------------------------------------------------- debug endpoint


class TestDebugAuditEndpoint:
    def _gateway(self, **kw):
        return HttpGateway(lambda key, n: (_ for _ in ()).throw(
            AssertionError("decide unused")), lambda k: None, **kw)

    def _get(self, port, path, token=None):
        req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
        if token:
            req.add_header("Authorization", f"Bearer {token}")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_not_wired_is_403(self):
        gw = self._gateway()
        gw.start()
        try:
            code, body = self._get(gw.port, "/debug/audit")
            assert code == 403 and "not enabled" in body["error"]
        finally:
            gw.shutdown()

    def test_bearer_gate_and_payload(self):
        payload = {"enabled": True, "false_deny_rate": 0.0,
                   "slo": {"windows": {}}}
        gw = self._gateway(audit_status=lambda: payload,
                           audit_token="s3cret")
        gw.start()
        try:
            code, _ = self._get(gw.port, "/debug/audit")
            assert code == 403
            code, body = self._get(gw.port, "/debug/audit", token="s3cret")
            assert code == 200 and body["enabled"] is True
        finally:
            gw.shutdown()


# ------------------------------------- combined /healthz composition


class TestHealthzComposition:
    """Satellite 4: no test exercised the FULL envelope with mesh +
    quarantine + audit (+ hh analytics + SLO) enabled at once — a real
    server subprocess proves the composition end to end."""

    def _spawn(self):
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.pathsep.join(
            [repo] + env.get("PYTHONPATH", "").split(os.pathsep))
        env["JAX_PLATFORMS"] = "cpu"
        from tests.netutil import free_port

        port, http_port = free_port(), free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "mesh", "--mesh-devices", "2", "--quarantine",
             "--audit", "--audit-sample", "1", "--audit-token", "tok",
             "--hh-slots", "16",
             "--sketch-depth", "2", "--sketch-width", "1024",
             "--sub-windows", "6", "--limit", "100", "--window", "60",
             "--max-batch", "256", "--no-prewarm", "--fail-open",
             "--port", str(port), "--http-port", str(http_port)],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True)
        line = proc.stdout.readline()
        if "serving" not in line:
            proc.kill()
            raise RuntimeError(f"server failed to start: {line!r}")
        return proc, port, http_port

    def test_full_envelope(self):
        proc, port, http_port = self._spawn()
        try:
            c = Client(port=port)
            c.allow_hashed(np.arange(1, 65, dtype=np.uint64))
            c.allow_batch([f"user:{i}" for i in range(32)])
            c.close()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/healthz",
                    timeout=10) as r:
                health = json.loads(r.read())
            # The composed envelope: every subsystem reports.
            assert health["serving"] is True
            assert "quarantine" in health
            assert health["audit"]["sample"] == 1
            assert "slo" in health and "windows" in health["slo"]
            assert "overload_periods" in health     # accuracy envelope
            assert "consumers" in health            # hh analytics
            # /debug/audit: gated, then the full observatory payload.
            req = urllib.request.Request(
                f"http://127.0.0.1:{http_port}/debug/audit")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 403
            req.add_header("Authorization", "Bearer tok")
            with urllib.request.urlopen(req, timeout=10) as r:
                dbg = json.loads(r.read())
            assert dbg["enabled"] is True
            assert dbg["samples"] >= 0
            assert "per_slice" in dbg and "slo" in dbg
            # /metrics carries the audit gauge families.
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/metrics",
                    timeout=10) as r:
                metrics = r.read().decode()
            assert "rate_limiter_audit_false_deny_rate" in metrics
            assert "rate_limiter_slo_burn_rate" in metrics
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()


# ------------------------------------ live audit == offline evaluation


class TestLiveAgreesWithOffline:
    def test_offline_rate_inside_the_live_wilson_interval(self):
        """The stream ``evaluation.evaluate_accuracy`` decides offline,
        driven through a real door with the auditor sampling one key in
        four: the population false-deny rate the offline run measures
        lies inside the live estimate's 95 % Wilson interval, the live
        tally is a strict sample of the stream, and once the seam is
        off the door taps nothing more."""
        from ratelimiter_tpu.evaluation import (
            evaluate_accuracy,
            zipf_key_ids,
        )

        n_keys, n_requests, batch, rate = 800, 3000, 512, 50_000.0
        sketch = SketchParams(depth=1, width=1 << 7, sub_windows=12)
        off = evaluate_accuracy(
            n_keys=n_keys, n_requests=n_requests, batch=batch, limit=20,
            request_rate=rate, sketch=sketch, seed=0, include_twin=False)
        # One narrow row collides: there is a rate to agree about.
        assert off.requests == n_requests
        assert 0 < off.false_denies_vs_oracle < off.oracle_allows

        # What evaluate_accuracy builds, field for field.
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, sketch=sketch,
                     limit=20, window=60.0, key_prefix="")
        ids = zipf_key_ids(n_keys, n_requests, 1.1, 0)

        async def run():
            clock = ManualClock(T0)
            lim = create_limiter(cfg, backend="sketch", clock=clock)
            srv = RateLimitServer(lim, max_batch=batch, max_delay=100e-6)
            await srv.start()
            auditor = audit.enable(cfg, sample=4, n_slices=1,
                                   include_twin=False)
            c = await AsyncClient.connect(srv.host, srv.port)
            for start in range(0, n_requests, batch):
                clock.set(T0 + start / rate)
                await c.allow_hashed(ids[start:start + batch])
            assert auditor.flush(timeout=30)
            st = auditor.status()
            audit.disable()
            # Off: the same door decides on, and no tap reaches the
            # (closed) auditor — its books stand where they stood.
            await c.allow_hashed(ids[:batch])
            late = auditor.status()
            await c.close()
            await srv.shutdown()
            lim.close()
            return st, late

        st, late = asyncio.run(run())
        assert audit.AUDITOR is None
        assert 0 < st["samples"] < n_requests
        assert st["dropped_frames"] == 0 and st["oracle_errors"] == 0
        lo, hi = st["false_deny_wilson95"]
        assert 0.0 <= lo <= off.false_deny_rate <= hi <= 1.0, (st, off)
        assert (late["samples"], late["audited_frames"]) == (
            st["samples"], st["audited_frames"])
