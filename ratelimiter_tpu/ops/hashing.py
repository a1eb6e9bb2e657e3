"""Host-side key hashing for the sketch backend.

The reference sends raw string keys over RESP and lets Redis hash them
internally; here keys are reduced to 64 bits at ingest (the serving tier's
job — SURVEY.md §7.4 hard part #4: "keys pre-hashed to u64 on host") and the
device only ever sees two 32-bit halves for Kirsch-Mitzenmacher double
hashing (ops/sketch_kernels._columns).

Two paths:
* strings  -> ratelimiter_tpu.native bulk hasher (word-at-a-time
  multiply-rotate, C++ kernel with a bit-identical vectorized NumPy twin):
  stable across processes/restarts, so checkpointed sketches stay
  addressable (tests/test_hashing has the cross-checks).
* uint64 ids -> splitmix64 finalizer, fully vectorized in NumPy — the fast
  path of the hashed wire lane and id-keyed tenants.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ratelimiter_tpu.native import bulk_hash_u64


def hash_strings_u64(keys: Sequence[str]) -> np.ndarray:
    """Stable 64-bit hashes of string keys (native bulk hasher)."""
    return bulk_hash_u64(keys)


def hash_prefixed_u64(keys: Sequence[str], prefix: str = "") -> np.ndarray:
    """THE key→hash rule: namespace prefix (exactly as it namespaces
    Redis keys in the reference, ``config.go:81-87``) then the bulk
    hash. One definition shared by the sketch backends
    (SketchLimiter._hash) and the audit tap's string lane
    (observability/audit.py) — if the formatting rule ever changes,
    both move together, or string-lane audit hashes would silently
    diverge from serving hashes."""
    if prefix:
        keys = [f"{prefix}:{k}" for k in keys]
    return bulk_hash_u64(keys)


def splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer: uniform 64-bit mixing of integer ids."""
    x = np.asarray(x, dtype=np.uint64).copy()
    with np.errstate(over="ignore"):
        x += np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x


def key_token(key: str) -> str:
    """Irreversible ``key#<16hex>`` token for logs and the control-plane
    event journal (the OPERATIONS §6 PII boundary). ONE definition —
    LoggingDecorator redaction and every journal emit site render keys
    through this, so redacted log lines and journal ``key_hash`` fields
    stay joinable. Hash-of-hash: ``hash_strings_u64`` feeds decisions
    and wire routing, so its raw value is quasi-public; the extra
    splitmix keeps tokens uncorrelatable with routing hashes."""
    return f"key#{int(splitmix64(hash_strings_u64([key]))[0]):016x}"


def split_hash(h64: np.ndarray, seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """(h1, h2) uint32 halves for double hashing; h2 forced odd so strides
    cycle the full power-of-two width. A seed remixes per-limiter so two
    sketches never share collision patterns."""
    h = h64
    if seed:
        h = splitmix64(h ^ np.uint64(seed & 0xFFFFFFFFFFFFFFFF))
    h1 = (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    h2 = ((h >> np.uint64(32)).astype(np.uint32)) | np.uint32(1)
    return h1, h2


# ------------------------------------------------------- device twins
#
# Bit-identical jnp forms of splitmix64 / split_hash, traced INSIDE the
# jitted decision step (ops/sketch_kernels.build_hashed_step), so the
# serving hot path stages one raw uint64 buffer per batch and the device
# does all per-key mixing — the host never touches per-key hash math
# (ADR-011). uint64 wrap-around semantics match NumPy exactly (jax x64
# is enabled by every entry point via ops.ensure_x64); the host/device
# agreement is fuzz-pinned by tests/test_hashing_device.py.

def splitmix64_dev(x):
    """jnp twin of splitmix64 (same constants, same wrap-around)."""
    import jax.numpy as jnp

    x = x.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def split_hash_dev(h64, seed: int = 0):
    """jnp twin of split_hash; ``seed`` is trace-time static (it is baked
    into the compiled step alongside the sketch geometry)."""
    import jax.numpy as jnp

    h = h64.astype(jnp.uint64)
    if seed:
        h = splitmix64_dev(h ^ jnp.uint64(seed & 0xFFFFFFFFFFFFFFFF))
    h1 = (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    h2 = (h >> jnp.uint64(32)).astype(jnp.uint32) | jnp.uint32(1)
    return h1, h2


# ----------------------------------------------------- splitmix64 inverse
#
# splitmix64 is a bijection on u64 (an odd-constant add, then three
# invertible xorshift-multiply rounds), so a FINALIZED hash can be taken
# back to the raw id that produced it. The fleet tier (ADR-017) uses this
# to forward already-finalized hashes over the plain T_ALLOW_HASHED wire
# lane — the receiver re-finalizes the recovered raw ids and lands on
# bit-identical hashes, so cross-host forwarding needs no new decision
# frame type. Fuzz-pinned round-trip in tests/test_fleet.py.

#: Modular inverses of the two splitmix64 multipliers mod 2^64.
_INV_C1 = np.uint64(pow(0xBF58476D1CE4E5B9, -1, 1 << 64))
_INV_C2 = np.uint64(pow(0x94D049BB133111EB, -1, 1 << 64))


def _unshift_right(x: np.ndarray, s: int) -> np.ndarray:
    """Invert ``y = x ^ (x >> s)`` (iterate to the fixpoint: each round
    recovers ``s`` more high-order-correct bits)."""
    y = x.copy()
    for _ in range(-(-64 // s) - 1):
        y = x ^ (y >> np.uint64(s))
    return y


def splitmix64_inv(x: np.ndarray) -> np.ndarray:
    """Exact inverse of :func:`splitmix64` (vectorized)."""
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        x = _unshift_right(x, 31)
        x = x * _INV_C2
        x = _unshift_right(x, 27)
        x = x * _INV_C1
        x = _unshift_right(x, 30)
        x = x - np.uint64(0x9E3779B97F4A7C15)
    return x
