"""The collective mesh router: one all_to_all dispatch per frame (ADR-024).

`MeshSpec.router="collective"` replaces the host's per-frame route work
(argsort by owner, per-slice sub-launches, scatter-back — ADR-013) with
ONE jitted shard_map dispatch: each device takes an even 1/n shard of
the frame, computes owners on device (`h64 % n`), routes rows to their
owning slice with `jax.lax.all_to_all`, runs the fused kernels on owned
rows, and routes results back to source order. Decisions are
bit-identical to the host router; the host's only per-frame route cost
is padding the frame to the shard shape. Run with a virtual mesh
anywhere:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python examples/20_collective_router.py

The served form (refused with --quarantine: one mesh-wide dispatch has
whole-mesh blast radius, so per-slice failure domains cannot hold):

    python -m ratelimiter_tpu.serving --backend mesh --mesh-devices 8 \
        --router collective --native --max-batch 16384
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if os.environ.get("JAX_PLATFORMS"):
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import jax

jax.config.update("jax_enable_x64", True)

if len(jax.devices()) < 4:
    print("SKIP: need >= 4 devices (see module docstring)")
    raise SystemExit(0)

import numpy as np

from ratelimiter_tpu import Algorithm, Config, ManualClock, SketchParams
from ratelimiter_tpu.core.config import MeshSpec
from ratelimiter_tpu.core.errors import InvalidConfigError
from ratelimiter_tpu import create_limiter

T0 = 1.7e9


def cfg(router):
    return Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5, window=60.0,
                  sketch=SketchParams(depth=2, width=1024, sub_windows=6),
                  mesh=MeshSpec(devices=4, router=router))


# The same mixed frames (keys spanning all 4 slices, a hot id recurring
# in-frame) through both routers: the all_to_all path must route every
# row to the same owner AND hand results back in frame order, so the
# hot id's sixth occurrence is denied at the same row either way.
host = create_limiter(cfg("host"), backend="mesh", clock=ManualClock(T0))
coll = create_limiter(cfg("collective"), backend="mesh",
                      clock=ManualClock(T0))

rng = np.random.default_rng(0)
for i in range(3):
    ids = rng.integers(1, 1 << 40, size=96, dtype=np.uint64)
    ids[::16] = np.uint64(0xBEEF)
    rh = host.allow_ids(ids, now=T0 + i * 0.5)
    rc = coll.allow_ids(ids, now=T0 + i * 0.5)
    np.testing.assert_array_equal(rh.allowed, rc.allowed)
    np.testing.assert_array_equal(rh.remaining, rc.remaining)
print("mixed frames: collective bit-identical to the host router")
print("router stats:", coll.router_stats())
assert coll.router_stats()["fallbacks"] == 0

# Skew beyond the bin headroom is never dropped: the device step
# commits nothing, the frame falls back to the host router exactly
# once, and the fallback is counted. (headroom < 1 forces capacity-1
# bins so a 4-copy frame must overflow.)
tight = create_limiter(
    Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5, window=60.0,
           sketch=SketchParams(depth=2, width=1024, sub_windows=6),
           mesh=MeshSpec(devices=4, router="collective",
                         bin_headroom=0.001)),
    backend="mesh", clock=ManualClock(T0))
hot = np.full(4, 0xF00D, dtype=np.uint64)
r = tight.allow_ids(hot, now=T0)
assert r.allowed.tolist() == [True] * 4
assert tight.router_stats()["fallbacks"] >= 1
print("overflow fallback: admission exact, fallbacks counted")
tight.close()

# Quarantine is refused loudly — one mesh-wide dispatch cannot honor
# per-slice failure domains (ADR-015 vs ADR-024).
try:
    create_limiter(
        Config(algorithm=Algorithm.SLIDING_WINDOW, limit=5, window=60.0,
               sketch=SketchParams(depth=2, width=1024),
               mesh=MeshSpec(devices=4, router="collective",
                             quarantine=True)),
        backend="mesh", clock=ManualClock(T0))
    raise AssertionError("collective+quarantine must be refused")
except InvalidConfigError as exc:
    print("quarantine refused:", str(exc)[:60], "...")

coll.close()
host.close()
print("OK")
