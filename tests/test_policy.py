"""Policy engine (ratelimiter_tpu/policy/): the device-resident override
table, its ops-level binary search, checkpoint/restore survival, the
config-fingerprint gate, the occupancy gauge, and the serving wire frames.

Backend-contract behavior (mixed batches, per-key limits/windows) lives in
tests/contract.py and runs per backend; this file covers the subsystem's
own pieces."""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys

import numpy as np
import pytest

from ratelimiter_tpu import (
    Algorithm,
    CheckpointError,
    Config,
    InvalidConfigError,
    ManualClock,
    create_limiter,
)
from ratelimiter_tpu.core.config import PolicySpec
from ratelimiter_tpu.ops import policy_kernels as pk
from ratelimiter_tpu.policy import PolicyTable
from tests.parent_lookup import inline_lookup

T0 = 1_700_000_000.0
BACKENDS = ("exact", "dense", "sketch")


def make(backend, algo=Algorithm.SLIDING_WINDOW, limit=4, window=60.0, **kw):
    clock = ManualClock(T0)
    cfg = Config(algorithm=algo, limit=limit, window=window, **kw)
    return create_limiter(cfg, backend=backend, clock=clock), clock


# ---------------------------------------------------------------- ops level

class TestLookupKernel:
    def _table(self, n, capacity, rng):
        keys = np.sort(rng.choice(2**62, size=n, replace=False)
                       .astype(np.int64))
        padded = np.full(capacity, pk.PAD_KEY, dtype=np.int64)
        padded[:n] = keys
        return keys, padded

    @pytest.mark.parametrize("capacity", [8, 64, 1024])
    def test_device_matches_host(self, capacity):
        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        n = capacity // 2
        keys, padded = self._table(n, capacity, rng)
        hits = rng.choice(keys, size=50)
        misses = rng.choice(2**62, size=50).astype(np.int64)
        queries = np.concatenate([hits, misses])
        d_idx, d_found = pk.lookup_i64(jnp.asarray(padded),
                                       jnp.asarray(queries))
        h_idx, h_found = pk.lookup_host(padded, queries)
        np.testing.assert_array_equal(np.asarray(d_found), h_found)
        # Where found, both must point at the matching row.
        np.testing.assert_array_equal(
            padded[np.asarray(d_idx)][np.asarray(d_found)],
            queries[np.asarray(d_found)])
        np.testing.assert_array_equal(padded[h_idx][h_found],
                                      queries[h_found])
        # All planted keys are found; random non-members are not (they
        # were drawn from a disjoint range with prob ~1).
        assert bool(np.all(np.asarray(d_found)[:50]))

    @pytest.mark.parametrize("capacity", [8, 64])
    def test_full_table_every_row_reachable(self, capacity):
        """Regression: the offset descent must reach index capacity-1 —
        a FULL table's max-key override was silently invisible to the
        kernels before the bounds-masked step-P probe."""
        import jax.numpy as jnp

        rng = np.random.default_rng(11)
        keys = np.sort(rng.choice(2**62, size=capacity, replace=False)
                       .astype(np.int64))
        idx, found = pk.lookup_i64(jnp.asarray(keys), jnp.asarray(keys))
        assert bool(np.all(np.asarray(found)))
        np.testing.assert_array_equal(np.asarray(idx),
                                      np.arange(capacity, dtype=np.int32))

    def test_full_limiter_table_max_key_decides(self):
        """End-to-end form of the same regression: fill the table to
        capacity and check the entry with the LARGEST search key still
        changes decisions."""
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=2,
                     window=60.0, policy=PolicySpec(capacity=8))
        lim = create_limiter(cfg, backend="dense", clock=clock)
        for i in range(8):
            lim.set_override(f"k{i}", 5)
        arrs = lim._policy_table.host_arrays()
        max_key = [k for k, _ in lim._policy_table.items()
                   if lim._policy_key(k) == int(arrs["key"][7])][0]
        out = lim.allow_batch([max_key] * 7)
        assert out.allow_count == 5, max_key
        lim.close()

    def test_empty_table_misses_everything(self):
        import jax.numpy as jnp

        empty = pk.empty_arrays(16, {"limit": 5})
        _, found = pk.lookup_i64(jnp.asarray(empty["key"]),
                                 jnp.asarray(np.arange(100, dtype=np.int64)))
        assert not bool(np.any(np.asarray(found)))

    def test_pack_halves_device_matches_host(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        h1 = rng.integers(0, 2**32, size=64, dtype=np.uint32)
        h2 = rng.integers(0, 2**32, size=64, dtype=np.uint32)
        dev = np.asarray(pk.pack_halves(jnp.asarray(h1), jnp.asarray(h2)))
        np.testing.assert_array_equal(dev, pk.pack_halves_host(h1, h2))


# ------------------------------------------------------------- table level

class TestPolicyTable:
    def _table(self, capacity=8, limit=4, window=60.0, **kw):
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=limit,
                     window=window, policy=PolicySpec(capacity=capacity))
        return PolicyTable(cfg, key_fn=lambda k: hash(k) & (2**62 - 1), **kw)

    def test_capacity_enforced(self):
        t = self._table(capacity=8)
        for i in range(8):
            t.set(f"k{i}", 10)
        with pytest.raises(InvalidConfigError, match="full"):
            t.set("overflow", 10)
        # Updating an existing entry is not a new slot.
        t.set("k0", 11)
        assert t.get("k0").limit == 11

    def test_spec_validation(self):
        with pytest.raises(InvalidConfigError):
            PolicySpec(capacity=12).validate()
        with pytest.raises(InvalidConfigError):
            PolicySpec(capacity=4).validate()
        PolicySpec(capacity=512).validate()

    def test_window_scaling_gate(self):
        t = self._table(window_scaling=False)
        with pytest.raises(InvalidConfigError, match="window"):
            t.set("k", 5, window_scale=0.5)
        t.set("k", 5)  # scale 1 is fine

    def test_effective_window_bounds(self):
        t = self._table(window=60.0)
        with pytest.raises(InvalidConfigError, match="window"):
            t.set("k", 5, window_scale=1e-9)

    def test_host_arrays_sorted_and_padded(self):
        t = self._table(capacity=8, limit=4)
        t.set("a", 7)
        t.set("b", 9)
        arrs = t.host_arrays()
        assert arrs["key"].shape == (8,)
        assert list(arrs["key"]) == sorted(arrs["key"])
        assert np.sum(arrs["key"] != pk.PAD_KEY) == 2
        # Padding rows carry defaults.
        assert arrs["limit"][-1] == 4

    def test_rebase_moves_defaults_only(self):
        t = self._table(limit=4)
        t.set("vip", 10)
        t.rebase(6, 60.0)
        arrs = t.host_arrays()
        assert arrs["limit"][-1] == 6            # default column moved
        assert t.get("vip").limit == 10          # entry pinned


# ----------------------------------------------------- limiter integration

class TestCheckpointRoundTrip:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_overrides_survive_restore(self, backend, tmp_path):
        lim, clock = make(backend)
        lim.set_override("vip", 9)
        lim.set_override("cheap", 2)
        lim.allow_batch(["vip"] * 5)
        path = str(tmp_path / "snap.npz")
        lim.save(path)
        lim2, _ = make(backend)
        lim2.restore(path)
        assert lim2.get_override("vip").limit == 9
        assert lim2.get_override("cheap").limit == 2
        assert lim2.override_count() == 2
        # Both the override AND the consumed quota restored: 4 of 9 left.
        assert lim2.allow_batch(["vip"] * 9).allow_count == 4
        lim.close()
        lim2.close()

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_policy_spec_mismatch_rejected(self, backend, tmp_path):
        """PolicySpec is part of the config fingerprint: a snapshot taken
        under a different override-table geometry must refuse to load."""
        lim, _ = make(backend)
        path = str(tmp_path / "snap.npz")
        lim.save(path)
        clock = ManualClock(T0)
        cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=4,
                     window=60.0, policy=PolicySpec(capacity=64))
        lim2 = create_limiter(cfg, backend=backend, clock=clock)
        with pytest.raises(CheckpointError, match="fingerprint"):
            lim2.restore(path)
        lim.close()
        lim2.close()

    def test_pre_policy_checkpoint_restores_empty_table(self, tmp_path):
        """Snapshots written before any override existed restore with an
        empty table (the policy_* columns are present but zero-length)."""
        lim, _ = make("exact")
        path = str(tmp_path / "snap.npz")
        lim.save(path)
        lim2, _ = make("exact")
        lim2.set_override("vip", 9)
        lim2.restore(path)
        assert lim2.override_count() == 0
        lim.close()
        lim2.close()


class TestOccupancyGauge:
    def test_gauge_tracks_mutations(self):
        from ratelimiter_tpu.observability import metrics as m

        lim, _ = make("exact")
        lim.set_override("a", 5)
        lim.set_override("b", 6)
        g = m.DEFAULT.get("rate_limiter_policy_overrides")
        assert g is not None and g.value() == 2.0
        lim.delete_override("a")
        assert g.value() == 1.0
        lim.close()

    def test_occupancy_in_metrics_text(self):
        from ratelimiter_tpu.observability import metrics as m

        lim, _ = make("exact")
        lim.set_override("a", 5)
        assert "rate_limiter_policy_overrides" in m.DEFAULT.render()
        lim.close()


class TestUpdateInteractions:
    def test_update_limit_moves_default_tier_only(self):
        lim, _ = make("exact", limit=4)
        lim.set_override("vip", 10)
        lim.update_limit(6)
        assert lim.allow_batch(["std"] * 8).allow_count == 6
        assert lim.allow_batch(["vip"] * 12).allow_count == 10
        lim.close()

    def test_update_window_blocked_with_scaled_overrides(self):
        lim, _ = make("exact", window=60.0)
        lim.set_override("fast", window_scale=0.5)
        with pytest.raises(InvalidConfigError, match="window-scaled"):
            lim.update_window(30.0)
        lim.delete_override("fast")
        lim.update_window(30.0)  # fine once the scaled entry is gone
        lim.close()

    def test_update_window_revalidates_overrides(self):
        """A window change that would push an existing override past the
        exact-integer overflow gates is refused BEFORE any state moves."""
        lim, _ = make("dense", algo=Algorithm.TOKEN_BUCKET, limit=10,
                      window=60.0)
        lim.set_override("vip", 4_000_000)  # fine at 60s
        with pytest.raises(InvalidConfigError, match="vip"):
            lim.update_window(3.15e7)       # ~1 year: W*num overflows
        assert lim.config.window == 60.0    # nothing migrated
        lim.close()

    def test_dense_override_validated_against_gates(self):
        lim, _ = make("dense", limit=4, window=60.0)
        with pytest.raises(InvalidConfigError):
            lim.set_override("huge", 1 << 50)
        lim.close()

    def test_sketch_override_f32_gate(self):
        lim, _ = make("sketch", algo=Algorithm.TPU_SKETCH)
        with pytest.raises(InvalidConfigError, match="2\\*\\*24"):
            lim.set_override("huge", 1 << 24)
        lim.close()


# ------------------------------------------------------------- wire frames

class TestWireProtocol:
    def test_policy_frames_roundtrip_encode_parse(self):
        from ratelimiter_tpu.serving import protocol as p

        frame = p.encode_policy_set(7, "vip", 9, 0.5)
        length, type_, rid = p.parse_header(frame[:p.HEADER_SIZE])
        assert type_ == p.T_POLICY_SET and rid == 7
        key, limit, scale = p.parse_policy_set(frame[p.HEADER_SIZE:])
        assert (key, limit, scale) == ("vip", 9, 0.5)
        # limit=None -> "keep default" flag
        frame = p.encode_policy_set(8, "w", None, 2.0)
        _, limit, scale = p.parse_policy_set(frame[p.HEADER_SIZE:])
        assert limit is None and scale == 2.0
        body = p.encode_policy_r(9, True, 9, 0.5)[p.HEADER_SIZE:]
        assert p.parse_policy_r(body) == (True, 9, 0.5)

    def test_server_policy_rpcs(self):
        """SET/GET/DEL over the asyncio server change live decisions."""
        from ratelimiter_tpu.serving import Client
        from ratelimiter_tpu.serving.server import RateLimitServer

        async def run():
            lim, _ = make("exact", limit=3)
            srv = RateLimitServer(lim, port=0)
            await srv.start()

            def client_ops():
                c = Client(port=srv.port)
                assert c.set_override("vip", 7) == (7, 1.0)
                assert c.get_override("vip") == (7, 1.0)
                assert c.get_override("other") is None
                allowed = sum(c.allow("vip").allowed for _ in range(9))
                assert allowed == 7
                assert c.allow("std").limit == 3
                assert c.delete_override("vip") is True
                assert c.delete_override("vip") is False
                with pytest.raises(InvalidConfigError):
                    c.set_override("bad", -1)
                c.close()

            await asyncio.get_running_loop().run_in_executor(None, client_ops)
            await srv.shutdown()
            lim.close()

        asyncio.run(run())


# --------------------------------------------------------------- x64 hygiene

class TestX64Hygiene:
    def test_import_leaves_x64_untouched(self):
        """Satellite: importing the library (and its kernel modules) must
        not flip the process-global jax_enable_x64 — that global changes
        dtype semantics for unrelated user JAX code."""
        code = (
            "import jax\n"
            "before = bool(jax.config.jax_enable_x64)\n"
            "import ratelimiter_tpu\n"
            "import ratelimiter_tpu.ops.dense_kernels\n"
            "import ratelimiter_tpu.ops.sketch_kernels\n"
            "import ratelimiter_tpu.ops.bucket_kernels\n"
            "import ratelimiter_tpu.ops.policy_kernels\n"
            "after = bool(jax.config.jax_enable_x64)\n"
            "assert before == after == False, (before, after)\n"
            "print('untouched')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "untouched" in out.stdout

    def test_device_limiter_requires_x64(self):
        """Construction (not some deep dispatch) fails loudly without the
        flag, naming the fix."""
        code = (
            "import jax\n"
            "from ratelimiter_tpu import Algorithm, Config, create_limiter\n"
            "cfg = Config(algorithm=Algorithm.TPU_SKETCH, limit=5,"
            " window=60.0)\n"
            "try:\n"
            "    create_limiter(cfg, backend='sketch')\n"
            "except RuntimeError as e:\n"
            "    assert 'jax_enable_x64' in str(e), e\n"
            "    print('raised')\n"
            "else:\n"
            "    raise SystemExit('no error raised')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "raised" in out.stdout

    def test_exact_backend_works_without_x64(self):
        code = (
            "from ratelimiter_tpu import Algorithm, Config, create_limiter\n"
            "cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5,"
            " window=60.0)\n"
            "lim = create_limiter(cfg, backend='exact')\n"
            "assert lim.allow('k').allowed\n"
            "print('exact ok')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr


# ------------------------------------- the step's occupancy branch (PR 31)
#
# The sketch steps take their per-row limit from
# policy_kernels.limit_for_rows: today's binary search under a lax.cond
# on the table's occupancy. Held here: the step with the helper equals
# the step with the lookup written inline as it was (PR 30), bit for bit
# in the packed verdicts (allowed, remaining, the bucket's retry_us) and
# in every state leaf — both rules, both lanes, three batch sizes, four
# table states, on one device, on the replicated mesh and under the
# collective router; the lowered programs keep every gather on the table
# inside that one conditional; and the counter says when it engages.

BRANCH_ALGOS = {"windowed": Algorithm.SLIDING_WINDOW,
                "bucket": Algorithm.TOKEN_BUCKET}
TABLES = ("empty", "one", "full", "emptied")
#: Override limits: far above and below the config's 3.
BIG, SMALL = 50, 1
HOT, HOT2, NEVER = "hot:0", "hot:1", "never:seen"


def _branch_limiter(algo):
    """``(cfg, limiter)`` of the small geometry these tests step."""
    from ratelimiter_tpu import SketchParams

    cfg = Config(algorithm=BRANCH_ALGOS[algo], limit=3, window=60.0,
                 sketch=SketchParams(depth=3, width=512, sub_windows=6))
    return cfg, create_limiter(cfg, backend="sketch", clock=ManualClock(T0))


def _fill(lim, table):
    """Bring ``lim``'s override table to one of the four states."""
    if table == "one":
        lim.set_override(HOT, BIG)
    elif table == "full":
        lim.set_override(HOT, BIG)
        lim.set_override(HOT2, SMALL)
        lim.set_override(NEVER, BIG)
        for i in range(lim.config.policy.capacity - 3):
            lim.set_override(f"filler:{i}", 7 + i % 5)
        assert len(lim._policy_table) == lim.config.policy.capacity
    elif table == "emptied":
        for key in (HOT, HOT2, NEVER):
            lim.set_override(key, BIG)
        for key in (HOT, HOT2, NEVER):
            assert lim.delete_override(key)


def _frames(lim, b, premix, seed):
    """Three instants of ``b`` ids: a quarter of each frame is the
    hottest key, a second hot key rides along, and the never-seen key
    arrives in the last frame only. On the premix lane the ids are the
    raw preimages of the same hashes (the step mixes them on device)."""
    from ratelimiter_tpu.ops.hashing import splitmix64_inv

    rng = np.random.default_rng(seed)
    hot, hot2, never = (int(x) for x in lim._hash([HOT, HOT2, NEVER]))
    for i, dt in enumerate((0.0, 0.3, 11.0)):
        ids = rng.integers(1, 1 << 62, size=b).astype(np.uint64)
        ids[: max(2, b // 4)] = hot
        ids[-2:] = hot2
        if i == 2:
            ids[b // 2] = never
        ns = rng.integers(1, 3, size=b).astype(np.int64)
        yield dt, (splitmix64_inv(ids) if premix else ids), ns


class TestOccupancyBranch:
    @pytest.mark.parametrize("table", TABLES)
    @pytest.mark.parametrize("b", [8, 1003, 4096])
    @pytest.mark.parametrize("premix", [False, True],
                             ids=["hashed", "premix"])
    @pytest.mark.parametrize("algo", list(BRANCH_ALGOS))
    def test_step_equals_step_with_theinline_lookup(self, algo, premix, b,
                                                     table, monkeypatch):
        from functools import partial

        import jax

        from ratelimiter_tpu.core.clock import to_micros
        from ratelimiter_tpu.ops import bucket_kernels, sketch_kernels

        cfg, lim = _branch_limiter(algo)
        kernels, body, rows = (
            (bucket_kernels, bucket_kernels._bucket_step_staged,
             bucket_kernels.BUCKET_ROWS) if algo == "bucket" else
            (sketch_kernels, sketch_kernels._sketch_step_staged,
             sketch_kernels.WINDOW_ROWS))
        _fill(lim, table)
        with lim._lock:
            policy = lim._policy_device()
        # The predicate is the device copy's own first row.
        assert (int(policy["key"][0]) == pk.PAD_KEY) == (
            table in ("empty", "emptied"))
        # The serving program itself against the same body traced with
        # the inline lookup (every call: a new shape traces again).
        new = kernels.build_hashed_step(cfg, premix=premix)
        old_jit = jax.jit(partial(body, seed=cfg.sketch.seed, premix=premix,
                                  **kernels.step_statics(cfg)))

        def old(*args):
            with monkeypatch.context() as m:
                m.setattr(pk, "limit_for_rows", inline_lookup)
                return old_jit(*args)

        padded = lim._padded_size(b)
        s_new, s_old = kernels.init_state(cfg), kernels.init_state(cfg)
        period = None
        hot_remaining = []
        admitted = denied = 0
        for dt, ids, ns in _frames(lim, b, premix, seed=b + premix):
            now_us = to_micros(T0 + dt)
            if algo != "bucket" and period != (
                    p := now_us // sketch_kernels.sketch_geometry(cfg)[1]):
                period = p              # the host's _sync_period
                roll = kernels.build_controls(cfg)[1]
                s_new, s_old = roll(s_new, np.int64(p)), roll(s_old,
                                                              np.int64(p))
            slot = np.zeros(2 * padded + 1, np.uint64)
            slot[:b] = ids
            slot.view(np.int64)[padded:padded + b] = ns
            slot.view(np.int64)[2 * padded] = now_us
            s_new, w_new = new(s_new, slot, policy)
            s_old, w_old = old(s_old, slot, policy)
            w_new, w_old = np.asarray(w_new), np.asarray(w_old)
            assert w_new.dtype == np.int32
            assert w_new.shape == (rows * padded,)
            np.testing.assert_array_equal(w_new, w_old)
            for k in s_old:
                np.testing.assert_array_equal(np.asarray(s_new[k]),
                                              np.asarray(s_old[k]), err_msg=k)
            allowed = w_new[:b] != 0
            remaining = w_new[padded:padded + b]
            admitted += int(allowed.sum())
            denied += b - int(allowed.sum())
            hot_remaining.append(int(remaining[0]))
        assert admitted and denied
        # The override decided something: the hottest key's first row has
        # BIG - n left under it, at most limit - 1 without.
        if table in ("one", "full"):
            assert hot_remaining[0] > cfg.limit
        else:
            assert hot_remaining[0] < cfg.limit
        lim.close()

    def test_a_lone_entry_that_packs_to_pad_key_reads_the_default(self):
        """The one table the predicate calls empty though it holds a row:
        the descent lands on the LAST padding row for such a key (module
        docstring), so both arms answer the default."""
        import jax.numpy as jnp

        P = 8
        key = np.full(P, pk.PAD_KEY, np.int64)
        limit = np.full(P, 3, np.int64)
        limit[0] = BIG                  # the entry's own row
        policy = {"key": jnp.asarray(key), "limit": jnp.asarray(limit)}
        ones = np.full(4, 0x7FFFFFFF, np.uint32), np.full(4, 0xFFFFFFFF,
                                                           np.uint32)
        h1, h2 = jnp.asarray(ones[0]), jnp.asarray(ones[1])
        assert int(pk.pack_halves_host(*ones)[0]) == pk.PAD_KEY
        got = pk.limit_for_rows(policy, h1, h2, 3, jnp.int64)
        want = inline_lookup(policy, h1, h2, 3, jnp.int64)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got), np.full(4, 3))

    @pytest.mark.parametrize("dtype", ["float32", "int64"])
    def test_the_helper_returns_the_callers_dtype_in_both_arms(self, dtype):
        import jax.numpy as jnp

        lim, _ = make("sketch", limit=3)
        h1 = jnp.arange(5, dtype=jnp.uint32)
        for fill in (False, True):
            if fill:
                lim.set_override(HOT, BIG)
            with lim._lock:
                policy = lim._policy_device()
            got = pk.limit_for_rows(policy, h1, h1, 3, jnp.dtype(dtype))
            assert got.dtype == jnp.dtype(dtype) and got.shape == (5,)
            np.testing.assert_array_equal(np.asarray(got), np.full(5, 3))
        lim.close()


# Every device program the benchmark's four configs launch, by
# tools/lowered_steps.py's names (33 at PR 30 and now): the serving step
# on both lanes, the replicated mesh's in both merges, the controls, and
# the routed step of the collective config.
_CONFIGS = ("bucket-c3", "cms-wide", "mesh4-c3", "mesh4-c3-coll")
_LANES = ("hashed", "premix")
SERVING = ([f"{c}.{lane}" for c in _CONFIGS for lane in _LANES]
           + [f"{c}.mesh-{m}.{lane}" for c in _CONFIGS
              for m in ("gather", "delta") for lane in _LANES]
           + [f"mesh4-c3-coll.routed-{lane}" for lane in _LANES])
CONTROLS = ([f"{c}.reset" for c in _CONFIGS]
            + [f"{c}.rotate" for c in _CONFIGS if c != "bucket-c3"])
#: The dense configurations' programs (ISSUE 43: the tool lowers them too,
#: so that a change of the dense state's layout shows as these and no other).
#: ``exact-tb-mesh4``'s five are ONE chip's (``capacity_a_chip``, ISSUE 51:
#: a slice's programs are a single table's).
DENSE = [f"{c}.{p}" for c in ("exact-tb-1m", "exact-tb-20m", "exact-tb-ttl",
                              "exact-tb-mesh4")
         for p in ("hashed", "premix", "reclaim", "forget", "clear_rem")]


def _spans(text, opener):
    """(start, end) of every ``opener(...) ({ ... })`` op of MLIR text:
    from the op's name to the parenthesis that closes its regions."""
    out, at = [], text.find(opener)
    while at != -1:
        i = text.index("({", at)
        depth = 0
        for j in range(i, len(text)):
            depth += text[j] in "({"
            depth -= text[j] in ")}"
            if depth == 0:
                break
        out.append((at, j))
        at = text.find(opener, j)
    return out


@pytest.fixture(scope="module")
def lowered():
    """name -> StableHLO text, lowered once on this process's CPU mesh."""
    import importlib.util
    import warnings
    from pathlib import Path

    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    repo = Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "lowered_steps", repo / "tools" / "lowered_steps.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return {name: fn.lower(*args).as_text()
                for name, fn, args in tool._programs(repo)}


class TestLoweredPrograms:

    def _table_gathers(self, text):
        import re

        capacity = PolicySpec().capacity
        return [m.start() for m in re.finditer(
            r'"stablehlo\.gather"\([^\n]*: \(tensor<%dxi64>, ' % capacity,
            text)]

    def test_one_program_a_shape_as_before(self, lowered):
        assert sorted(lowered) == sorted(SERVING + CONTROLS + DENSE)
        assert len(lowered) == 33 + 20

    @pytest.mark.parametrize("name", SERVING + CONTROLS + DENSE)
    def test_every_program_is_the_pinned_text(self, lowered, name):
        """``tests/data/lowered_programs/index.json`` is the index
        ``tools/lowered_steps.py --out`` writes: every one-chip and
        host-router program's entry there was lowered from PR 44's
        checkout (ISSUE 45 changed the routed launch's operands and
        nothing else: those two entries are its own). A PR that means to
        change a program lowers again, copies the index over this file
        and says which entries moved; one that does not is held to the
        parent's text here."""
        import hashlib
        import json
        from pathlib import Path

        pinned = json.loads((Path(__file__).parent / "data"
                             / "lowered_programs" / "index.json").read_text())
        assert sorted(pinned) == sorted(lowered)
        assert hashlib.sha256(lowered[name].encode()).hexdigest() \
            == pinned[name]["sha256"], name

    @pytest.mark.parametrize("lane", _LANES)
    def test_the_routed_step_takes_one_batch_operand(self, lowered, lane):
        """The collective launch's frame is ONE operand, ``uint64[n, 2L +
        2]`` sharded by rows (a row a device): no second column, and no
        scalar for ``jit`` to replicate on every call."""
        import re

        text = lowered[f"mesh4-c3-coll.routed-{lane}"]
        head = text[text.index("func.func public @main("):]
        head = head[:head.index(") -> (")]
        operands = re.findall(r"%arg\d+: tensor<([^>]*)>", head)
        rows = 256 // 4   # the tool's frame over the config's four slices
        assert [t for t in operands if t.endswith("ui64")] == [
            f"4x{2 * rows + 2}xui64"]
        assert not [t for t in operands if "x" not in t], \
            "a scalar operand reaches the routed step"
        assert not [t for t in operands if t.startswith("256x")], \
            "a frame column rides beside the staged operand"

    @pytest.mark.parametrize("name", SERVING)
    def test_every_table_gather_is_inside_the_one_conditional(self, lowered,
                                                              name):
        text = lowered[name]
        gathers = self._table_gathers(text)
        # log2(1024) + 1 probes, the equality read and the limit column.
        assert len(gathers) == 13
        cases = [(a, z) for a, z in _spans(text, '"stablehlo.case"')
                 if any(a < g < z for g in gathers)]
        assert len(cases) == 1, "exactly one conditional holds the lookup"
        a, z = cases[0]
        assert all(a < g < z for g in gathers), \
            "a gather on the override table outside the conditional"
        # The predicate is the table's own first key against PAD_KEY.
        head = text[:a]
        assert str(pk.PAD_KEY) in head[head.rindex("stablehlo.slice"):]
        # Two arms; the other one gathers nothing.
        arms = text[a:z].split("}, {")
        assert len(arms) == 2
        assert sum("stablehlo.gather" in arm for arm in arms) == 1

    @pytest.mark.parametrize("name", CONTROLS)
    def test_controls_take_no_table(self, lowered, name):
        assert self._table_gathers(lowered[name]) == []
        assert '"stablehlo.case"' not in lowered[name]

    @pytest.mark.parametrize("algo", list(BRANCH_ALGOS))
    def test_the_reference_of_these_tests_is_the_unconditional_lookup(
            self, algo, monkeypatch):
        """The inline form the equality tests compare against really is
        the parent's: the same 13 gathers, no conditional around them."""
        from functools import partial

        import jax

        from ratelimiter_tpu.ops import bucket_kernels, sketch_kernels

        cfg, lim = _branch_limiter(algo)
        kernels, body = ((bucket_kernels, bucket_kernels._bucket_step_staged)
                         if algo == "bucket" else
                         (sketch_kernels, sketch_kernels._sketch_step_staged))
        with lim._lock:
            policy = lim._policy_device()
        monkeypatch.setattr(pk, "limit_for_rows", inline_lookup)
        text = jax.jit(partial(body, seed=0, premix=False,
                               **kernels.step_statics(cfg))).lower(
            kernels.init_state(cfg), np.zeros(2 * 64 + 1, np.uint64),
            policy).as_text()
        gathers = self._table_gathers(text)
        assert len(gathers) == 13
        assert not any(a < g < z for g in gathers
                       for a, z in _spans(text, '"stablehlo.case"'))
        lim.close()

    def test_no_vmap_wraps_a_step(self):
        """A batched cond runs both arms: nothing in the program may
        vmap a step body."""
        from pathlib import Path

        import ratelimiter_tpu

        root = Path(ratelimiter_tpu.__file__).parent
        hits = [str(p) for p in root.rglob("*.py")
                if "vmap(" in p.read_text()]
        assert hits == []
