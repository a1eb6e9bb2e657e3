"""Observability decorators: contract conformance + emitted metrics/logs.

The reference specs the decorators but never builds them
(``docs/ADR/003-decorator-pattern-for-observability.md:44-125``); its
planned test — "decorated limiter passes the same suite" — is realized
here by instantiating the full contract suite over a metrics+logging
decorated exact limiter.
"""

import logging

import numpy as np
import pytest

from tests.contract import ContractTests

from ratelimiter_tpu import Algorithm, Config, ManualClock, create_limiter
from ratelimiter_tpu.observability import (
    LoggingDecorator,
    MetricsDecorator,
    Registry,
)


class TestDecoratedContract(ContractTests):
    """The whole contract suite through a decorator stack — decorators must
    be semantically invisible (ADR/003's composability requirement)."""

    backend = "exact"

    def make_limiter(self, config, clock):
        inner = create_limiter(config, backend="exact", clock=clock)
        return MetricsDecorator(LoggingDecorator(inner), Registry())


def make(algo=Algorithm.SLIDING_WINDOW, limit=5, window=60.0, backend="exact",
         **kw):
    clock = ManualClock(1_700_000_000.0)
    cfg = Config(algorithm=algo, limit=limit, window=window, **kw)
    reg = Registry()
    lim = MetricsDecorator(create_limiter(cfg, backend=backend, clock=clock), reg)
    return lim, reg, clock


class TestMetricsDecorator:
    def test_requests_by_result(self):
        lim, reg, _ = make(limit=2)
        lim.allow("k")
        lim.allow("k")
        lim.allow("k")  # denied
        c = reg.get("rate_limiter_requests_total")
        assert c.value(algorithm="sliding_window", result="allowed") == 2
        assert c.value(algorithm="sliding_window", result="denied") == 1
        assert reg.get("rate_limiter_decisions_allowed_total").value(
            algorithm="sliding_window") == 2
        assert reg.get("rate_limiter_decisions_denied_total").value(
            algorithm="sliding_window") == 1
        lim.close()

    def test_batch_counts_decisions(self):
        lim, reg, _ = make(limit=3)
        out = lim.allow_batch(["a"] * 5)
        assert out.allow_count == 3
        assert reg.get("rate_limiter_decisions_allowed_total").value(
            algorithm="sliding_window") == 3
        assert reg.get("rate_limiter_decisions_denied_total").value(
            algorithm="sliding_window") == 2
        h = reg.get("rate_limiter_batch_size")
        assert h.count() == 1 and h.sum() == 5.0
        lim.close()

    def test_latency_histogram_observes(self):
        lim, reg, _ = make()
        lim.allow("k")
        h = reg.get("rate_limiter_latency_seconds")
        assert h.count(algorithm="sliding_window", op="allow_n") == 1
        assert h.sum(algorithm="sliding_window", op="allow_n") > 0
        lim.close()

    def test_invalid_n_counted_as_error(self):
        from ratelimiter_tpu import InvalidNError

        lim, reg, _ = make()
        with pytest.raises(InvalidNError):
            lim.allow_n("k", 0)
        c = reg.get("rate_limiter_requests_total")
        assert c.value(algorithm="sliding_window", result="error:invalid_n") == 1
        lim.close()

    def test_fail_open_counted_as_storage_error(self):
        lim, reg, _ = make(backend="sketch", algo=Algorithm.TPU_SKETCH,
                           fail_open=True)
        lim.inject_failure()  # __getattr__ pass-through to the sketch backend
        res = lim.allow("k")
        assert res.allowed and res.fail_open
        assert reg.get("rate_limiter_storage_errors_total").value(
            algorithm="tpu_sketch") == 1
        c = reg.get("rate_limiter_requests_total")
        assert c.value(algorithm="tpu_sketch", result="fail_open") == 1
        lim.close()

    def test_fail_closed_counted_as_storage_error(self):
        from ratelimiter_tpu import StorageUnavailableError

        lim, reg, _ = make(backend="sketch", algo=Algorithm.TPU_SKETCH,
                           fail_open=False)
        lim.inject_failure()
        with pytest.raises(StorageUnavailableError):
            lim.allow("k")
        assert reg.get("rate_limiter_storage_errors_total").value(
            algorithm="tpu_sketch") == 1
        lim.close()

    def test_prometheus_rendering(self):
        lim, reg, _ = make(limit=1)
        lim.allow("k")
        lim.allow("k")
        text = reg.render()
        assert "# TYPE rate_limiter_requests_total counter" in text
        assert ('rate_limiter_requests_total{algorithm="sliding_window",'
                'result="allowed"} 1') in text
        assert "# TYPE rate_limiter_latency_seconds histogram" in text
        assert "rate_limiter_latency_seconds_bucket" in text
        assert 'le="+Inf"' in text
        lim.close()


#: (backend, rule) of each kind of limiter that keeps its state on a device.
DEVICE_BACKENDS = {
    "dense": ("dense", Algorithm.TOKEN_BUCKET),
    "sketch": ("sketch", Algorithm.SLIDING_WINDOW),
    "bucket": ("sketch", Algorithm.TOKEN_BUCKET),
    "mesh": ("mesh", Algorithm.SLIDING_WINDOW),
}


class TestDeviceMemoryGauges:
    """``rate_limiter_state_resident_bytes{shard,device}`` and the two
    ``device.memory_stats()`` gauges (ISSUE 42), beside the
    ``rate_limiter_directory_*`` gauges: set at scrape by a collect hook,
    for every backend that keeps its state on a device."""

    @staticmethod
    def limiter(name):
        from ratelimiter_tpu import DenseParams, SketchParams

        backend, algo = DEVICE_BACKENDS[name]
        return make(algo=algo, backend=backend,
                    dense=DenseParams(capacity=4096),
                    sketch=SketchParams(depth=2, width=1024))

    @pytest.mark.parametrize("name", list(DEVICE_BACKENDS))
    def test_resident_bytes_are_the_state_leaves_nbytes(self, name):
        import jax

        from ratelimiter_tpu.observability.decorators import undecorated

        lim, reg, _ = self.limiter(name)
        base = undecorated(lim)
        units = base.sub_limiters() if name == "mesh" else [base]
        want: dict = {}
        for unit in units:
            leaves = jax.tree_util.tree_leaves(undecorated(unit)._state)
            (dev,) = leaves[0].devices()
            want[str(dev.id)] = sum(leaf.nbytes for leaf in leaves)
        assert len(want) == (len(jax.devices()) if name == "mesh" else 1)
        lim.allow_batch([f"k{i}" for i in range(40)])   # donates the state
        text = reg.render()
        gauge = reg.get("rate_limiter_state_resident_bytes")
        for device, nbytes in want.items():
            assert gauge.value(shard="0", device=device) == nbytes > 0
        assert len(gauge._values) == len(want)
        # The CPU keeps no memory statistics: the families are declared
        # and carry NO sample — absent, not 0.
        for family in ("rate_limiter_device_peak_bytes",
                       "rate_limiter_device_bytes_in_use"):
            assert f"# TYPE {family} gauge" in text
            assert f"\n{family}" not in text
            assert reg.get(family)._values == {}
        lim.close()
        assert lim._collect_device_memory not in reg._collect_hooks
        assert lim._collect_directory not in reg._collect_hooks

    def test_where_the_platform_keeps_statistics_they_are_samples(
            self, monkeypatch):
        """A device whose ``memory_stats()`` answers (the TPU's): one
        sample a device, no shard label."""
        lim, reg, _ = self.limiter("dense")

        class Chip:
            id = 3

            @staticmethod
            def memory_stats():
                return {"peak_bytes_in_use": 2_836_000_000,
                        "bytes_in_use": 2_200_000_000, "bytes_limit": 1}

        monkeypatch.setattr(lim._resident, "state_resident_bytes",
                            lambda: {Chip: 2_147_483_672})
        text = reg.render()
        assert ('rate_limiter_state_resident_bytes{device="3",shard="0"} '
                '2147483672') in text
        assert 'rate_limiter_device_peak_bytes{device="3"} 2836000000' in text
        assert 'rate_limiter_device_bytes_in_use{device="3"} 2200000000' \
            in text
        lim.close()

    @pytest.mark.parametrize("name", ["dense", "sketch", "bucket"])
    def test_the_scrape_hook_waits_for_no_dispatch(self, name):
        """The hook takes no limiter lock and reads no buffer: it returns
        while a launch holds the limiter's lock."""
        import threading

        lim, reg, _ = self.limiter(name)
        base = lim._resident
        done = threading.Event()
        with base._lock:
            worker = threading.Thread(
                target=lambda: (lim._collect_device_memory(), done.set()),
                daemon=True)
            worker.start()
            assert done.wait(timeout=10.0)
        assert reg.get("rate_limiter_state_resident_bytes")._values
        lim.close()


class TestLoggingDecorator:
    def test_decisions_logged_at_debug(self, caplog):
        clock = ManualClock(0.0)
        cfg = Config(algorithm=Algorithm.FIXED_WINDOW, limit=1, window=60.0)
        lim = LoggingDecorator(create_limiter(cfg, clock=clock))
        with caplog.at_level(logging.DEBUG, logger="ratelimiter_tpu"):
            lim.allow("k")
            lim.allow("k")
        msgs = [r.message for r in caplog.records]
        assert any("allowed=True" in s for s in msgs)
        assert any("allowed=False" in s for s in msgs)
        lim.close()

    def test_fail_open_logged_at_warning(self, caplog):
        clock = ManualClock(0.0)
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=5, window=60.0,
                     fail_open=True)
        lim = LoggingDecorator(create_limiter(cfg, backend="sketch", clock=clock))
        lim.inject_failure()
        with caplog.at_level(logging.WARNING, logger="ratelimiter_tpu"):
            lim.allow("k")
        assert any(r.levelno == logging.WARNING and "fail-open" in r.message
                   for r in caplog.records)
        lim.close()

    def test_errors_logged_at_error(self, caplog):
        from ratelimiter_tpu import InvalidNError

        clock = ManualClock(0.0)
        cfg = Config(algorithm=Algorithm.FIXED_WINDOW, limit=1, window=60.0)
        lim = LoggingDecorator(create_limiter(cfg, clock=clock))
        with caplog.at_level(logging.ERROR, logger="ratelimiter_tpu"):
            with pytest.raises(InvalidNError):
                lim.allow_n("k", -1)
        assert any(r.levelno == logging.ERROR for r in caplog.records)
        lim.close()


class TestTracingDecorator:
    def test_contract_preserved_and_capture_writes(self, tmp_path):
        from ratelimiter_tpu.observability import TracingDecorator

        clock = ManualClock(0.0)
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=3, window=60.0)
        lim = TracingDecorator(create_limiter(cfg, backend="sketch",
                                              clock=clock))
        # Semantics unchanged through the annotation wrapper.
        for expect in (True, True, True, False):
            assert lim.allow("k").allowed is expect
        lim.reset("k")
        assert lim.allow("k").allowed
        # capture() produces an xplane trace directory.
        out = str(tmp_path / "trace")
        with lim.capture(out):
            lim.allow_batch(["a", "b", "c"])
        import os

        assert any("plugins" in d or f for d, _, f in os.walk(out)), \
            "profiler capture wrote nothing"
        lim.close()


class TestDecoratorComposition:
    def test_stack_order_is_transparent(self):
        clock = ManualClock(0.0)
        cfg = Config(algorithm=Algorithm.TOKEN_BUCKET, limit=3, window=30.0)
        reg = Registry()
        lim = LoggingDecorator(
            MetricsDecorator(create_limiter(cfg, clock=clock), reg))
        for expect in (True, True, True, False):
            assert lim.allow("k").allowed is expect
        assert reg.get("rate_limiter_decisions_allowed_total").value(
            algorithm="token_bucket") == 3
        lim.close()

    def test_passthrough_extras(self):
        # Backend-specific surface (allow_hashed) stays reachable.
        clock = ManualClock(0.0)
        cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=100, window=60.0)
        lim = MetricsDecorator(
            create_limiter(cfg, backend="sketch", clock=clock), Registry())
        out = lim.allow_hashed(np.arange(8, dtype=np.uint64))
        assert out.allow_count == 8
        lim.close()
