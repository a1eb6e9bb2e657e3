"""The benchmark matrix: algorithms x backends x request shapes.

Reference analog: ``*_bench_test.go`` (31 benchmarks, SURVEY.md §2.1 row
12). Dimensions and their mapping:

| reference benchmark            | here                                      |
|--------------------------------|-------------------------------------------|
| BenchmarkX_Allow               | scalar: allow() loop, one key             |
| BenchmarkX_AllowN(1/10/100)    | scalar: allow_n(n) loop                   |
| BenchmarkX_AllowParallel       | batch: allow_batch over many keys (the    |
|                                | TPU concurrency story IS the batch)       |
| BenchmarkX_KeyCardinality(k)   | batch over k distinct keys                |
| BenchmarkX_Denied              | saturated key, denial path                |
| BenchmarkX_FailOpen            | injected backend failure, fail-open path  |
| BenchmarkX_Reset               | reset() loop                              |
| BenchmarkX_WindowSizes         | window 1s / 60s / 3600s                   |
| (new) batch_hot                | one batch, duplicate hot key (in-batch    |
|                                | sequencing cost)                          |
| (new) hashed fast path         | allow_hashed, pre-hashed u64 keys         |
| (new) string hashing           | native bulk hasher throughput             |

Each cell: one warmup call (compile), then timed iterations. Output is a
list of row dicts (benchmarks/__main__.py renders them).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np

from ratelimiter_tpu import Algorithm, Config, ManualClock, SketchParams, create_limiter

T0 = 1_700_000_000.0

ALGOS = {
    "fixed_window": Algorithm.FIXED_WINDOW,
    "sliding_window": Algorithm.SLIDING_WINDOW,
    "token_bucket": Algorithm.TOKEN_BUCKET,
}
BACKENDS = ("exact", "dense", "sketch")


def _mk(algo: Algorithm, backend: str, limit=1_000_000, window=60.0, **kw):
    """High default limit so throughput cells measure the mechanism, not
    denial mixes (denial cells set their own tight limits)."""
    cfg = Config(algorithm=algo, limit=limit, window=window,
                 sketch=SketchParams(depth=4, width=65536), **kw)
    return create_limiter(cfg, backend=backend, clock=ManualClock(T0))


def _time(fn: Callable[[], object], *, min_s: float = 0.25,
          max_iters: int = 10_000) -> tuple[float, int]:
    """(seconds_per_call, iterations). One untimed warmup (jit compile)."""
    fn()
    iters = 0
    t0 = time.perf_counter()
    while True:
        fn()
        iters += 1
        dt = time.perf_counter() - t0
        if dt >= min_s or iters >= max_iters:
            return dt / iters, iters


def _row(group: str, algo: str, backend: str, shape: str,
         sec_per_call: float, decisions_per_call: int, iters: int,
         device_us: float | None = ...) -> Dict:
    row = {
        "group": group,
        "algorithm": algo,
        "backend": backend,
        "shape": shape,
        "us_per_call": round(sec_per_call * 1e6, 2),
        "decisions_per_sec": round(decisions_per_call / sec_per_call, 1),
        "iters": iters,
    }
    if device_us is not ...:
        # Key present = the cell WAS supposed to be measured: None (or a
        # measurement that rounds to nothing) records an explicit failed
        # measurement the renderer prints as n/a — never a silent 0.0.
        val = round(device_us, 2) if device_us is not None else None
        row["device_us"] = val if val else None
    return row


def _measure_rtt_s() -> float:
    """One trivial dispatch+sync: the host<->device round trip a single
    us_per_call dispatch pays."""
    import jax.numpy as jnp

    y = (jnp.zeros((8,), jnp.int32) + 1)
    np.asarray(y)
    t0 = time.perf_counter()
    y = (jnp.zeros((8,), jnp.int32) + 2)
    np.asarray(y)
    return time.perf_counter() - t0


def _device_step_us(cfg, backend: str, batch: int, card: int, *,
                    steps: int = 64, reps: int = 2) -> float | None:
    """Amortized on-device time of one batched step for this cell.

    The matrix's wall-clock ``us_per_call`` pays a full host round trip
    per dispatch — an environment property, not a kernel property
    (VERDICT r3 weak item 3). This column runs a T-step on-device scan
    (one dispatch for T steps), chains ``reps`` of them asynchronously,
    syncs once, and subtracts the measured round trip: what is left is
    device compute per step at this batch shape. None for host backends
    — and None when the RTT subtraction leaves nothing measurable (an
    RTT sample larger than the whole chained run): a 0.0 here is a
    failed measurement, not a free kernel. Renderers print ``n/a`` for
    None.
    """
    import jax.numpy as jnp

    from ratelimiter_tpu.ops import bucket_kernels, dense_kernels, sketch_kernels
    from ratelimiter_tpu.ops.hashing import split_hash, splitmix64

    rng = np.random.default_rng(7)
    t0_us = int(T0 * 1e6)
    if backend == "sketch":
        ids = rng.integers(1, max(card, 2),
                           size=(steps, batch)).astype(np.uint64)
        h1, h2 = split_hash(splitmix64(ids.reshape(-1)), cfg.sketch.seed)
        h1s = jnp.asarray(h1.reshape(steps, batch))
        h2s = jnp.asarray(h2.reshape(steps, batch))
        ns = jnp.ones((steps, batch), jnp.int32)
        if cfg.algorithm is Algorithm.TOKEN_BUCKET:
            scan = bucket_kernels.build_scan(cfg)
            state = bucket_kernels.init_state(cfg)
        else:
            scan = sketch_kernels.build_scan(cfg)
            _, sub_us, _, _, _ = sketch_kernels.sketch_geometry(cfg)
            _, roll = sketch_kernels.build_controls(cfg)
            state = roll(sketch_kernels.init_state(cfg),
                         jnp.int64(t0_us // sub_us))
        args = (h1s, h2s, ns)
    elif backend == "dense":
        cap = cfg.dense.capacity
        sids = jnp.asarray(rng.integers(0, min(card, cap), size=(steps, batch)),
                           jnp.int32)
        ns = jnp.asarray(np.ones((steps, batch), np.int64))
        scan = dense_kernels.build_scan(cfg)
        state = dense_kernels.init_state(cfg.algorithm, cap, cfg.limit)
        args = (sids, ns)
    else:
        return None

    dt_us = 100  # steps*dt stays inside one sub-window (sketch precondition)
    state, packed, _ = scan(state, *args, jnp.int64(t0_us), jnp.int64(dt_us))
    np.asarray(packed.ravel()[:1])  # compile + settle
    rtt_s = _measure_rtt_s()
    t0 = time.perf_counter()
    for r in range(1, reps + 1):
        state, packed, _ = scan(state, *args,
                                jnp.int64(t0_us + r * steps * dt_us),
                                jnp.int64(dt_us))
    np.asarray(packed.ravel()[:1])
    dt = time.perf_counter() - t0
    if dt <= rtt_s:
        # The measurement failed (round-trip noise swallowed the run):
        # refuse to report a silent zero — callers render None as n/a.
        return None
    return (dt - rtt_s) / (reps * steps) * 1e6


def run_matrix(quick: bool = False, log=print) -> List[Dict]:
    rows: List[Dict] = []
    backends = ("exact", "sketch") if quick else BACKENDS
    batch = 1024 if quick else 4096

    for algo_name, algo in ALGOS.items():
        for backend in backends:
            # ---- scalar allow / allow_n (host-path latency floor)
            for n in (1, 10, 100):
                lim = _mk(algo, backend)
                keys = [f"user:{i}" for i in range(100)]
                i = 0

                def call():
                    nonlocal i
                    lim.allow_n(keys[i % 100], n)
                    i += 1

                spc, iters = _time(call, min_s=0.1 if quick else 0.25)
                rows.append(_row("allow_n", algo_name, backend, f"n={n}",
                                 spc, n, iters))
                lim.close()
            log(f"matrix: {algo_name}/{backend} scalar done")

            # ---- batched decisions across key cardinality
            for card in (10, 1000) if quick else (10, 100, 1000, 100_000):
                if backend == "dense" and card > 50_000:
                    continue  # beyond default slot capacity by design
                lim = _mk(algo, backend)
                rng = np.random.default_rng(0)
                key_batch = [f"user:{i}" for i in
                             rng.integers(0, card, size=batch)]

                def call():
                    lim.allow_batch(key_batch)

                spc, iters = _time(call, min_s=0.1 if quick else 0.25)
                dev_us = _device_step_us(lim.config, backend, batch, card)
                rows.append(_row("batch", algo_name, backend,
                                 f"B={batch},keys={card}", spc, batch, iters,
                                 device_us=dev_us))
                lim.close()
            log(f"matrix: {algo_name}/{backend} batch done")

            # ---- one batch, duplicate hot key (in-batch sequencing)
            lim = _mk(algo, backend)
            hot = ["hot"] * batch

            def call():
                lim.allow_batch(hot)

            spc, iters = _time(call, min_s=0.1 if quick else 0.25)
            dev_us = _device_step_us(lim.config, backend, batch, 1)
            rows.append(_row("batch_hot", algo_name, backend, f"B={batch}",
                             spc, batch, iters, device_us=dev_us))
            lim.close()

            # ---- denied path (key saturated; every decision is a deny)
            lim = _mk(algo, backend, limit=1)
            lim.allow("sat")

            def call():
                lim.allow("sat")

            spc, iters = _time(call, min_s=0.1 if quick else 0.25)
            rows.append(_row("denied", algo_name, backend, "scalar",
                             spc, 1, iters))
            lim.close()

            # ---- reset
            lim = _mk(algo, backend)

            def call():
                lim.allow("k")
                lim.reset("k")

            spc, iters = _time(call, min_s=0.1 if quick else 0.25)
            rows.append(_row("reset", algo_name, backend, "allow+reset",
                             spc, 1, iters))
            lim.close()

            # ---- fail-open path (backend down, policy allows)
            if backend in ("dense", "sketch"):
                lim = _mk(algo, backend, fail_open=True)
                lim.allow("k")  # compile before injecting the failure
                lim.inject_failure()

                def call():
                    lim.allow("k")

                spc, iters = _time(call, min_s=0.05)
                rows.append(_row("fail_open", algo_name, backend, "scalar",
                                 spc, 1, iters))
                lim.close()

        # ---- window sizes (sketch backend; ring size differs per window)
        if not quick:
            for window in (1.0, 60.0, 3600.0):
                lim = _mk(algo, "sketch", window=window)
                keys = [f"user:{i}" for i in range(1000)]
                rng = np.random.default_rng(1)
                kb = [keys[j] for j in rng.integers(0, 1000, size=batch)]

                def call():
                    lim.allow_batch(kb)

                spc, iters = _time(call, min_s=0.25)
                dev_us = _device_step_us(lim.config, "sketch", batch, 1000)
                rows.append(_row("window_size", algo_name, "sketch",
                                 f"W={window:g}s,B={batch}", spc, batch, iters,
                                 device_us=dev_us))
                lim.close()
            log(f"matrix: {algo_name} window sizes done")

    # ---- sketch hashed fast path (u64 keys, no string handling)
    for algo_name in ("sliding_window", "token_bucket"):
        lim = _mk(ALGOS[algo_name], "sketch")
        h = np.random.default_rng(2).integers(
            0, 2 ** 63, size=batch).astype(np.uint64)

        def call():
            lim.allow_hashed(h)

        spc, iters = _time(call, min_s=0.1 if quick else 0.25)
        dev_us = _device_step_us(lim.config, "sketch", batch, batch)
        rows.append(_row("hashed", algo_name, "sketch", f"B={batch}",
                         spc, batch, iters, device_us=dev_us))
        lim.close()

    # ---- native string hashing throughput (host ingest stage)
    from ratelimiter_tpu.native import bulk_hash_u64, native_available

    keys = [f"user:{i}:project:{i % 97}" for i in range(batch)]

    def call():
        bulk_hash_u64(keys)

    spc, iters = _time(call, min_s=0.1)
    rows.append({
        "group": "string_hash",
        "algorithm": "-",
        "backend": "native" if native_available() else "numpy-fallback",
        "shape": f"B={batch}",
        "us_per_call": round(spc * 1e6, 2),
        "decisions_per_sec": round(batch / spc, 1),
        "iters": iters,
    })
    log("matrix: hashing done")
    return rows
