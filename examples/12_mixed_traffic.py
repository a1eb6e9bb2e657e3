"""Mixed traffic on a sliced mesh: the scatter-gather scheduler (ADR-013).

MIXED frames — frames whose keys span several device slices, what any
un-sharded load balancer sends — used to fork-join across every device
queue and collapsed under load. The scheduler splits each frame
once, coalesces every frame that arrives within one batching window
into ONE dispatch per touched device, and answers each frame from its
row range of the window result. Run with a virtual mesh on any host:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python examples/12_mixed_traffic.py

The served form (the C++ loadgen's slice-spread knob drives the same
shape: spread=1 affine .. spread=n uniform mixed):

    python -m ratelimiter_tpu.serving --backend mesh --mesh-devices 8 \
        --native --inflight 1 --max-batch 16384 --max-delay-us 1000
"""

import os

if os.environ.get("JAX_PLATFORMS"):
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import jax

jax.config.update("jax_enable_x64", True)

if len(jax.devices()) < 4:
    print("SKIP: need >= 4 devices (see module docstring)")
    raise SystemExit(0)

import asyncio

import numpy as np

from ratelimiter_tpu import Algorithm, Config, ManualClock, SketchParams
from ratelimiter_tpu.algorithms.sketch import SketchLimiter
from ratelimiter_tpu.observability import Registry
from ratelimiter_tpu.parallel import SlicedMeshLimiter
from ratelimiter_tpu.serving import MicroBatcher

T0 = 1.7e9
cfg = Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5, window=60.0,
             sketch=SketchParams(depth=2, width=1024, sub_windows=6))
mesh = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)

# Six "clients" each submit a MIXED frame (ids spanning all 4 slices)
# in the same batching window. The micro-batcher concatenates them in
# arrival order, launches ONCE (= one padded sub-dispatch per touched
# device), and resolves each client's future from its own row range.
rng = np.random.default_rng(0)
hot = np.uint64(0xBEEF)
frames = []
for _ in range(6):
    ids = rng.integers(1, 1 << 40, size=64, dtype=np.uint64)
    ids[::16] = hot                     # a hot id recurring across frames
    frames.append(ids)

reg = Registry()


async def clients():
    b = MicroBatcher(mesh, max_batch=1 << 14, max_delay=2e-3,
                     inflight=4, registry=reg)
    futs = [b.submit_hashed_nowait(f, np.ones(64, dtype=np.int64))
            for f in frames]
    outs = await asyncio.gather(*futs)
    await b.drain()
    b.close()
    return outs


outs = asyncio.run(clients())
dispatches = reg.get("rate_limiter_server_batch_size").count()
print(f"{len(frames)} mixed frames of 64 ids -> {dispatches} window "
      f"dispatch(es); each client got its own {len(outs[0])}-row result")

# Same-key ordering is ARRIVAL order across the coalesced frames: the
# hot id appears 4x per frame, 24x in the window, limit=5 — exactly the
# FIRST five occurrences are admitted, counted across frame boundaries.
hot_decisions = np.concatenate([o.allowed[f == hot]
                                for o, f in zip(outs, frames)])
assert hot_decisions.sum() == 5 and bool(np.all(hot_decisions[:5]))
print(f"hot id across the window: {hot_decisions[:8].tolist()}... "
      "(first 5 admitted, arrival-ordered)")

# The decisions are bit-identical to single-device oracles fed each
# slice's ids in arrival order — coalescing changes the batching, not
# the decision stream.
window = np.concatenate(frames)
allowed = np.concatenate([o.allowed for o in outs])
owners = mesh.owner_of_id(window)
for dev in range(4):
    idx = np.flatnonzero(owners == dev)
    oracle = SketchLimiter(cfg, ManualClock(T0))
    np.testing.assert_array_equal(allowed[idx],
                                  oracle.allow_ids(window[idx]).allowed)
    oracle.close()
print("bit-identical to per-slice single-device oracles")

# Embedders batching their own frames use the same seam directly:
# launch the window, slice the result — views, no copies. (A fresh
# mesh, because the batcher above already consumed the hot id's quota.)
mesh2 = SlicedMeshLimiter(cfg, ManualClock(T0), n_devices=4)
res = mesh2.resolve(mesh2.launch_ids(window, wire=True))
first = res.rows(0, 64)                  # client 0's rows
assert first.remaining.base is not None  # a view over the window result
np.testing.assert_array_equal(first.allowed, outs[0].allowed)
print("BatchResult.rows(): zero-copy per-frame views of one window")

mesh2.close()
mesh.close()
print("OK")
