"""Accuracy-envelope enforcement: load-aware geometry sizing
(SketchParams.for_load), the calibrated mass budget, and the runtime
undersized-geometry watchdog (VERDICT r3 item 3; the reference sizes its
backend explicitly, docs/ADR/001:183-187)."""

import logging

import pytest

from netutil import free_port

from ratelimiter_tpu import (
    Algorithm,
    Config,
    InvalidConfigError,
    ManualClock,
    SketchParams,
    create_limiter,
)


class TestForLoad:
    def test_width_scales_with_mass(self):
        small = SketchParams.for_load(100, 1_000_000)
        big = SketchParams.for_load(100, 100_000_000)
        assert big.width > small.width
        # Powers of two, valid geometries.
        small.validate()
        big.validate()

    def test_width_scales_inversely_with_limit(self):
        tight = SketchParams.for_load(10, 10_000_000)
        loose = SketchParams.for_load(10_000, 10_000_000)
        assert tight.width > loose.width

    def test_stricter_target_needs_more_width(self):
        lax = SketchParams.for_load(100, 50_000_000, target_false_deny=0.01)
        strict = SketchParams.for_load(100, 50_000_000,
                                       target_false_deny=0.0001)
        assert strict.width > lax.width

    def test_budget_roundtrip(self):
        """A geometry sized for mass M at the 1% target has a budget that
        admits M (the watchdog must not cry wolf at the design point)."""
        for mass in (1e5, 1e7, 2.4e8):
            p = SketchParams.for_load(100, mass, target_false_deny=0.01)
            assert p.mass_budget(100) >= mass

    def test_config3_literal_geometry_is_declared_undersized(self):
        """The BASELINE config-3 literal geometry (d=4 w=65536) is far too
        narrow for saturation traffic (its false-deny rate there: not
        measured on this round's code). Its budget must declare
        saturation mass (~100M admitted) far out of envelope."""
        literal = SketchParams(depth=4, width=65536)
        assert literal.mass_budget(100) < 100_000_000 / 5

    def test_memory_gate(self):
        with pytest.raises(InvalidConfigError, match="max_state_bytes"):
            SketchParams.for_load(1, 10 ** 12,
                                  max_state_bytes=64 << 20)

    def test_active_keys_floor(self):
        """Occupancy regime: width floors at one cell per active key even
        when the mass curve alone would allow less (the measured 1M-key
        2^19-cell false-deny excursion, config.py class comment)."""
        mass_only = SketchParams.for_load(100, 1_000_000)
        floored = SketchParams.for_load(100, 1_000_000,
                                        active_keys=1_000_000)
        assert floored.width >= 1_000_000
        assert floored.width > mass_only.width

    def test_safety_and_validation(self):
        wide = SketchParams.for_load(100, 1_000_000, safety=8.0)
        base = SketchParams.for_load(100, 1_000_000)
        assert wide.width > base.width
        with pytest.raises(InvalidConfigError):
            SketchParams.for_load(0, 1000)
        with pytest.raises(InvalidConfigError):
            SketchParams.for_load(100, 0)
        with pytest.raises(InvalidConfigError):
            SketchParams.for_load(100, 1000, target_false_deny=0.9)
        with pytest.raises(InvalidConfigError):
            SketchParams.for_load(100, 1000, depth=2)


class TestMassWatchdog:
    def _lim(self, width=16, limit=5, sub_windows=6, window=6.0):
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=limit,
                     window=window, max_batch_admission_iters=1,
                     sketch=SketchParams(depth=3, width=width,
                                         sub_windows=sub_windows))
        return create_limiter(cfg, backend="sketch",
                              clock=ManualClock(1_700_000_000.0))

    def test_overload_warns_once_per_subwindow(self, caplog):
        lim = self._lim()
        budget = lim.mass_budget           # 2 * 5 * 16 = 160
        assert budget == 160
        with caplog.at_level(logging.WARNING, logger="ratelimiter_tpu"):
            # Admitted mass: distinct keys, 1 req each -> all allowed.
            for start in (0, 200):
                lim.allow_batch([f"k{start + i}" for i in range(200)])
        warnings = [r for r in caplog.records
                    if "geometry undersized" in r.message]
        assert len(warnings) == 1          # same sub-window: warned once
        assert lim.overload_periods == 1
        assert lim.in_window_admitted_mass() > budget
        # A later sub-window still overloaded -> warns again.
        lim.clock.advance(1.1)
        with caplog.at_level(logging.WARNING, logger="ratelimiter_tpu"):
            lim.allow_batch([f"j{i}" for i in range(200)])
        warnings = [r for r in caplog.records
                    if "geometry undersized" in r.message]
        assert len(warnings) == 2
        lim.close()

    def test_mass_expires_with_the_window(self):
        lim = self._lim()
        lim.allow_batch([f"k{i}" for i in range(100)])
        assert lim.in_window_admitted_mass() == 100
        lim.clock.advance(7.0)             # > window: all periods pruned
        lim.allow("fresh")
        assert lim.in_window_admitted_mass() == 1
        lim.close()

    def test_within_budget_never_warns(self, caplog):
        lim = self._lim(width=1024)        # budget 10240
        with caplog.at_level(logging.WARNING, logger="ratelimiter_tpu"):
            for _ in range(3):
                lim.allow_batch([f"k{i}" for i in range(300)])
        assert not [r for r in caplog.records
                    if "geometry undersized" in r.message]
        assert lim.overload_periods == 0
        lim.close()

    def test_denied_requests_do_not_count(self):
        lim = self._lim(width=64, limit=3)
        for _ in range(10):
            lim.allow("hot")
        # Only the 3 admitted decisions contribute mass.
        assert lim.in_window_admitted_mass() == 3
        lim.close()

    def test_token_bucket_excluded(self):
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=5, window=6.0,
                     sketch=SketchParams(depth=3, width=16))
        lim = create_limiter(cfg, backend="sketch",
                             clock=ManualClock(1_700_000_000.0))
        for i in range(50):
            lim.allow(f"k{i}")             # must not touch the watchdog
        lim.close()

    def test_budget_follows_dynamic_limit(self):
        lim = self._lim(width=64, limit=5)
        assert lim.mass_budget == 2 * 5 * 64
        lim.update_limit(50)
        assert lim.mass_budget == 2 * 50 * 64
        lim.close()


class TestStrictOverloadPolicy:
    """overload_policy="strict": a mis-sized geometry surfaces in
    DECISIONS (bounded extra denies), not just logs (VERDICT r4 weak 6 /
    next-round item 8)."""

    def _lim(self, policy="strict", width=16, limit=5):
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=limit,
                     window=6.0, max_batch_admission_iters=1,
                     sketch=SketchParams(depth=3, width=width, sub_windows=6,
                                         overload_policy=policy))
        return create_limiter(cfg, backend="sketch",
                              clock=ManualClock(1_700_000_000.0))

    def test_over_budget_rejects_new_admissions(self):
        lim = self._lim()
        budget = lim.mass_budget                       # 160
        out = lim.allow_batch([f"k{i}" for i in range(200)])
        assert int(out.allowed.sum()) == 200           # filled the budget
        out = lim.allow_batch([f"m{i}" for i in range(10)])
        assert int(out.allowed.sum()) == 0             # strict: reject all
        assert (out.retry_after > 0).all()
        assert lim.overload_periods >= 1
        # Mass did NOT grow past the overload point.
        assert lim.in_window_admitted_mass() == 200 > budget
        lim.close()

    def test_recovers_as_history_expires(self):
        lim = self._lim()
        lim.allow_batch([f"k{i}" for i in range(200)])
        assert int(lim.allow("x").allowed) == 0
        lim.clock.advance(7.0)                         # full window passes
        assert lim.allow("x").allowed                  # budget clear again
        lim.close()

    def test_warn_policy_keeps_admitting(self):
        lim = self._lim(policy="warn")
        lim.allow_batch([f"k{i}" for i in range(200)])
        out = lim.allow_batch([f"m{i}" for i in range(10)])
        assert int(out.allowed.sum()) == 10            # degraded, serving
        lim.close()

    def test_invalid_policy_rejected(self):
        with pytest.raises(InvalidConfigError, match="overload_policy"):
            SketchParams(overload_policy="explode").validate()

    def test_metrics_gauges_exported(self):
        from ratelimiter_tpu.observability import MetricsDecorator, Registry

        reg = Registry()
        lim = MetricsDecorator(self._lim(policy="warn"), registry=reg)
        lim.allow_batch([f"k{i}" for i in range(200)])
        text = reg.render()
        assert 'rate_limiter_sketch_overload_periods{shard="0"} 1' in text
        assert ('rate_limiter_sketch_in_window_admitted_mass{shard="0"} 200'
                in text)
        assert 'rate_limiter_sketch_mass_budget{shard="0"} 160' in text
        lim.close()

    def test_healthz_surfaces_overload(self):
        """The server binary's /healthz carries the envelope fields."""
        import json
        import os
        import signal as sig
        import socket
        import subprocess
        import sys
        import urllib.request

        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        env["JAX_PLATFORMS"] = "cpu"


        port, http_port = free_port(), free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m", "ratelimiter_tpu.serving",
             "--backend", "sketch", "--algorithm", "sliding_window",
             "--limit", "5", "--window", "60",
             "--sketch-depth", "3", "--sketch-width", "16",
             "--no-prewarm", "--port", str(port),
             "--http-port", str(http_port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            for _ in range(10):
                line = proc.stdout.readline()
                if line.startswith("serving"):
                    break
            from ratelimiter_tpu.serving import Client

            with Client(port=port, timeout=30.0) as c:
                c.allow_batch([f"k{i}" for i in range(200)], [1] * 200)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/healthz") as r:
                health = json.loads(r.read())
            assert health["overload_periods"] >= 1
            assert health["in_window_admitted_mass"] > health["mass_budget"]
            assert health["overload_policy"] == "warn"
            proc.send_signal(sig.SIGTERM)
            assert proc.wait(timeout=15) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
