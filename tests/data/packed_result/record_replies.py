#!/usr/bin/env python3
"""The replies of one fixed frame set, as whichever tree is on PYTHONPATH
writes them (PR 29's fixture; recorded once on the parent, commit
7a28f20, on the CPU with 8 virtual devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        JAX_ENABLE_X64=1 python tests/data/packed_result/record_replies.py OUT.npz

For each rule (sliding window, token bucket), each unit (one chip; the
collective router over four devices), each instant and each frame size,
with an explicit clock:

* ``native/…``: the five byte strings the native door's completer hands
  to C++ (``NativeRateLimitServer._pack_result``) for a hashed-lane frame;
* ``wire/…``: the asyncio door's whole ``T_RESULT_HASHED`` reply frame
  (``protocol.encode_result_hashed``) for a raw-id frame launched
  ``wire=True`` — framed from ``BatchResult.wire_packed``.

tests/test_packed_result_replies.py replays the set on the current tree.
"""
import sys

import numpy as np

from ratelimiter_tpu import Algorithm, Config, ManualClock, SketchParams
from ratelimiter_tpu.algorithms.sketch import (
    SketchLimiter,
    SketchTokenBucketLimiter,
)
from ratelimiter_tpu.core.config import MeshSpec
from ratelimiter_tpu.parallel import CollectiveMeshLimiter
from ratelimiter_tpu.serving import protocol
from ratelimiter_tpu.serving.native_server import NativeRateLimitServer

T0 = 1_700_000_000.25
INSTANTS = (0.0, 0.7, 39.9, 40.2)       # the window ends at T0 + 39.75
SIZES = (8, 41, 203)
RULES = {"window": Algorithm.SLIDING_WINDOW, "bucket": Algorithm.TOKEN_BUCKET}
UNITS = ("chip", "collective")
NATIVE_PARTS = ("flags", "remaining", "retry_after", "reset_at")


def build(rule: str, unit: str):
    sketch = SketchParams(depth=3, width=1024, sub_windows=6)
    if unit == "collective":
        return CollectiveMeshLimiter(
            Config(algorithm=RULES[rule], limit=4, window=60.0, sketch=sketch,
                   mesh=MeshSpec(devices=4, router="collective")),
            ManualClock(T0), n_devices=4)
    cls = SketchTokenBucketLimiter if rule == "bucket" else SketchLimiter
    return cls(Config(algorithm=RULES[rule], limit=4, window=60.0,
                      sketch=sketch), ManualClock(T0))


def replies() -> dict:
    """{name: uint8 array} of every reply of the frame set."""
    out = {}
    for rule in RULES:
        for unit in UNITS:
            lim = build(rule, unit)
            rng = np.random.default_rng(2929)
            for k, dt in enumerate(INSTANTS):
                for size in SIZES:
                    ids = rng.integers(1, size // 3 + 2,
                                       size=size).astype(np.uint64)
                    ns = rng.integers(1, 3, size=size).astype(np.int64)
                    tag = f"{rule}/{unit}/{k}/{size}"
                    res = lim.resolve(lim.launch_hashed(ids, ns,
                                                        now=T0 + dt))
                    parts = NativeRateLimitServer._pack_result(None, res)
                    for name, blob in zip(NATIVE_PARTS, parts):
                        out[f"native/{tag}/{name}"] = np.frombuffer(
                            blob, dtype=np.uint8)
                    out[f"native/{tag}/limit"] = np.array([parts[4]])
                    res = lim.resolve(lim.launch_ids(ids, ns, now=T0 + dt,
                                                     wire=True))
                    assert res.wire_packed is not None
                    out[f"wire/{tag}"] = np.frombuffer(
                        protocol.encode_result_hashed(7, res), dtype=np.uint8)
            lim.close()
    return out


if __name__ == "__main__":
    np.savez_compressed(sys.argv[1], **replies())
