"""Device-side override-table lookup — the policy engine's hot-path half.

The policy engine (ratelimiter_tpu/policy/) keeps per-key limit/window
overrides in a fixed-capacity, device-resident table: a SORTED int64 key
array plus parallel value columns. Every decision step consults it with
the branchless binary search below, so a batch mixing default and
overridden keys is still decided in ONE fused dispatch — no per-key host
lookup, no dynamic shapes, no recompiles when entries change (only the
array *contents* change; capacity is the compiled shape).

What a probe costs on the chip: a TPU runs a gather as a sequential
loop over its rows (ops/sortmerge.py), and a 64-bit gather moves two
32-bit words on a chip without 64-bit vectors. The descent below is
log2(capacity) + 1 dependent probes of ``int64[B]`` plus one gather of
the limit column — 12-13 serialized int64 gathers a row at the default
capacity of 1,024, more than the rule's own state costs a row (d gathers
+ d scatters). So the sketch steps take their per-row limit from
``limit_for_rows``, which runs the descent under a device-side branch
only when the table holds an entry (PERF.md §6, PR 31, has the
measured size); an empty table, the common deployment, costs one scalar
read.

Key domain: each backend reduces a key to an int64 "search key" host-side
at override-set time (policy/table.py):

* dense backend: the native bulk hash of the formatted key
  (ops/hashing.hash_strings_u64), bit-cast to int64;
* sketch backends: the (h1, h2) uint32 halves the CMS columns are
  derived from, packed as ``(h1 << 32) | h2`` and bit-cast — so the
  query can be packed on device from the operands the step already has,
  and no extra per-request operand crosses the host/device boundary.

Both sides (sort at build time, search at query time) use the SAME int64
total order, so the uint64->int64 bit-cast reordering is harmless.

Padding rows hold PAD_KEY (int64 max) with default values; a search miss
therefore also lands on default values, making ``found`` advisory for
observability rather than load-bearing for correctness.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: Padding sentinel for unused table rows. A real key hashing to exactly
#: int64-max would match a padding row and read the DEFAULT values — the
#: same decision it would get from a miss (2^-64 per key, and harmless).
PAD_KEY = (1 << 63) - 1


def lookup_i64(table_keys, queries):
    """Branchless binary search: for each query, the index of its match in
    the sorted ``table_keys`` (int64[P], P a power of two, padded with
    PAD_KEY) and whether it matched.

    Returns ``(idx int32[B], found bool[B])`` where idx is safe to gather
    with even on misses (clamped to [0, P-1]).
    """
    import jax.numpy as jnp

    P = table_keys.shape[0]
    assert P & (P - 1) == 0, f"table capacity must be a power of two, got {P}"
    # Classic offset descent: after the loop, idx is the largest i with
    # table_keys[i] <= q (or -1 when every entry is greater). The step
    # sequence starts at P (not P/2) with an explicit bounds mask so the
    # LAST row is reachable — steps summing to P-1 from idx=-1 would top
    # out at P-2 and a FULL table would silently lose its max-key entry.
    idx = jnp.full(queries.shape, -1, jnp.int32)
    step = P
    while step >= 1:
        cand = idx + step
        in_range = cand <= P - 1
        probe = table_keys[jnp.minimum(cand, P - 1)] <= queries
        idx = jnp.where(in_range & probe, cand, idx)
        step //= 2
    safe = jnp.maximum(idx, 0)
    found = (idx >= 0) & (table_keys[safe] == queries)
    return safe, found


def limit_for_rows(policy, h1, h2, default: int, dtype):
    """Per-row effective limit ``dtype[B]`` for the sketch steps: the
    override of each row's (h1, h2) key, ``default`` where it has none.

    The ONE definition both rules call. The descent runs inside a
    ``lax.cond`` on the table's occupancy, read from the device copy
    itself: the table is sorted with its PAD_KEY rows last
    (policy/table.py), so ``policy["key"][0] == PAD_KEY`` iff no row can
    change an answer — an empty table, or one whose only entry packs to
    exactly PAD_KEY, which the descent resolves to the LAST padding row
    and therefore to ``default`` as well (module docstring). Both arms
    give the same values in every case; the empty arm gathers nothing.
    One compiled step per shape either way, and nothing on the host to
    go out of step with the device copy when set_override /
    delete_override race a launch (ADR-008 addendum).

    Never wrap a step in ``vmap``: a batched cond runs both arms.
    """
    import jax
    import jax.numpy as jnp

    def lookup():
        pidx, pfound = lookup_i64(policy["key"], pack_halves(h1, h2))
        return jnp.where(pfound, policy["limit"][pidx],
                         jnp.int64(default)).astype(dtype)

    def no_entries():
        return jnp.full(h1.shape, default, dtype)

    return jax.lax.cond(policy["key"][0] != PAD_KEY, lookup, no_entries)


def lookup_host(table_keys: np.ndarray, queries: np.ndarray,
                ) -> Tuple[np.ndarray, np.ndarray]:
    """NumPy twin of lookup_i64 (same contract) for host-side result
    assembly and tests."""
    idx = np.searchsorted(table_keys, queries, side="right").astype(np.int64) - 1
    safe = np.maximum(idx, 0).astype(np.int32)
    found = (idx >= 0) & (table_keys[safe] == queries)
    return safe, found


def pack_halves(h1, h2):
    """Device-side (h1, h2) uint32 -> int64 search key, bit-identical to
    policy/table.py's host packing (uint64 ``(h1 << 32) | h2`` bit-cast)."""
    import jax
    import jax.numpy as jnp

    packed = (h1.astype(jnp.uint64) << jnp.uint64(32)) | h2.astype(jnp.uint64)
    return jax.lax.bitcast_convert_type(packed, jnp.int64)


def pack_halves_host(h1: np.ndarray, h2: np.ndarray) -> np.ndarray:
    """Host twin of pack_halves."""
    packed = (h1.astype(np.uint64) << np.uint64(32)) | h2.astype(np.uint64)
    return packed.view(np.int64)


def empty_arrays(capacity: int, defaults: Dict[str, int]) -> Dict[str, np.ndarray]:
    """An all-padding host table: ``key`` int64[capacity] of PAD_KEY plus
    one int64 column per default value. Every lookup misses (or reads
    defaults), so an empty table is behaviorally a no-op."""
    out = {"key": np.full(capacity, PAD_KEY, dtype=np.int64)}
    for name, val in defaults.items():
        out[name] = np.full(capacity, int(val), dtype=np.int64)
    return out
