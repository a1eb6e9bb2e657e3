"""Sort-merge table access: gather/scatter-free reads and histogram writes.

TPU has no hardware gather/scatter; XLA lowers both to ~7 ns/element
sequential loops, which made the naive CMS hot path scatter-bound
(measured on-chip: scatter/gather ~7 ns/elem vs lax.sort ~0.3-1 ns/elem
and cumsum ~0.2 ns/elem). These helpers express "read table[col] for a
batch of cols" and "table[col] += add" as *sorts plus cumsums* instead:

* mix-sort: concatenate the w table cells (key ``2*c``) with the B batch
  elements (key ``2*col + 1``) and stable-sort; every batch element lands
  immediately after its cell.
* read (``row_gather``): delta-encode the table row (``diff`` with
  prepend 0), carry deltas as sort payload, cumsum over the merged order —
  the running sum at a batch element's position is exactly ``row[col]``.
* write (``row_histogram``): carry per-request adds as payload, cumsum;
  the running sum at cell ``c`` is the total of adds with ``col < c``;
  a second "unmix" sort brings cells back into dense col order and a diff
  yields the per-cell histogram to add densely.
* unmix-sort: key ``is_batch ? (w + src_index) : col`` restores original
  batch order (reads) or dense cell order (writes) in one stable sort.

Cost per call: 2 sorts of (w + B) + O(w + B) vector work — independent of
key duplication, no sequential memory loop anywhere. This is the moral
equivalent of Redis pipelining all commands of a batch through one pass
over the keyspace, and it is what makes the ``allow_batch`` hot path
(SURVEY.md §7.4 hard part #4) MXU/VPU-friendly.

All functions are shape-polymorphic in B and w but jit-static per shape.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ratelimiter_tpu.core.jaxcfg import on_tpu
from ratelimiter_tpu.ops.scans import cumsum_fast


def _use_sortmerge(B: int, w: int) -> bool:
    """Static strategy choice (trace-time). Sort-merge pays two sorts of
    (w + B) — every sort carries the whole table — while direct indexing
    pays ~7-10 ns per batch element, sequential-on-TPU. Measured on v5e
    in July (d=3, w=2^20, full step): direct wins 2.2x at B=64K, ties
    near B=256K, sort-merge wins 1.7x at B=1M; crossover is where B's
    serialized gather cost overtakes the table-dominated sort cost, i.e.
    B ~ w/2. On this round's program the direct side of that comparison
    at B = 64K is measured (PERF.md section 5, PR 35, the served step at
    d=3, w=2^20): 3,678 us, of which the indexed accesses are 45.7 ns a
    row (six 32-bit gathers / scatter-adds at 7.6 ns) on 126 us of fixed
    cost; the sort-merge side and the crossover have not been run since
    July (no cell has B >= w/2). CPU/GPU backends have native
    gather/scatter — always direct there."""
    return on_tpu() and B >= max(64, w // 2)


def _mix_keys(col: jnp.ndarray, w: int) -> jnp.ndarray:
    """int32[(w+B,)] merge keys: cell c -> 2c, batch element -> 2*col+1."""
    cells = (jax.lax.iota(jnp.int32, w) * 2)
    batch = col.astype(jnp.int32) * 2 + 1
    return jnp.concatenate([cells, batch])


@jax.named_scope("sortmerge_gather")
def row_gather(rows: Sequence[jnp.ndarray], col: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """Read ``row[col]`` for each row in ``rows`` at a common (B,) col vector.

    Returns a tuple of (B,) arrays in the original batch order. All rows
    must share shape (w,); integer dtypes are propagated exactly (delta
    encoding telescopes back losslessly in int32).
    """
    w = rows[0].shape[0]
    B = col.shape[0]
    if not _use_sortmerge(B, w):
        return tuple(r[col] for r in rows)
    key = _mix_keys(col, w)
    zeros_b = jnp.zeros((B,), rows[0].dtype)
    deltas = [jnp.concatenate([jnp.diff(r, prepend=r.dtype.type(0)), zeros_b])
              for r in rows]
    # src: batch elements carry their original index, cells carry -1.
    src = jnp.concatenate([jnp.full((w,), -1, jnp.int32),
                           jax.lax.iota(jnp.int32, B)])
    sorted_ops = jax.lax.sort((key, src, *deltas), num_keys=1, is_stable=True)
    s_src = sorted_ops[1]
    props = [cumsum_fast(d) for d in sorted_ops[2:]]
    # Unmix: batch entries first, ordered by original index.
    ukey = jnp.where(s_src >= 0, s_src, B + (sorted_ops[0] >> 1))
    unmixed = jax.lax.sort((ukey, *props), num_keys=1, is_stable=True)
    return tuple(u[:B] for u in unmixed[1:])


@jax.named_scope("sortmerge_histogram")
def row_histogram(col: jnp.ndarray, add: jnp.ndarray, w: int) -> jnp.ndarray:
    """Dense (w,) histogram H with ``H[c] = sum(add[col == c])``.

    The caller applies it with a vectorized ``row + H`` — no scatter.
    """
    B = col.shape[0]
    if not _use_sortmerge(B, w):
        return jnp.zeros((w,), add.dtype).at[col].add(add)
    key = _mix_keys(col, w)
    payload = jnp.concatenate([jnp.zeros((w,), add.dtype), add])
    s_key, s_pay = jax.lax.sort((key, payload), num_keys=1, is_stable=True)
    run = cumsum_fast(s_pay)
    is_cell = (s_key & 1) == 0
    # Cells first in dense col order; batch entries pushed to the tail.
    ukey = jnp.where(is_cell, s_key >> 1, w + jax.lax.iota(jnp.int32, w + B))
    _, u_run = jax.lax.sort((ukey, run), num_keys=1, is_stable=True)
    a_less = u_run[:w]          # adds with col < c, for each cell c
    total = run[-1]
    return jnp.diff(a_less, append=total[None])


@jax.named_scope("sortmerge_histogram_max")
def row_histogram_max(col: jnp.ndarray, val: jnp.ndarray, w: int) -> jnp.ndarray:
    """Dense (w,) per-column maxima: ``M[c] = max(val[col == c])``, 0 where
    a column has no entries. ``val`` must be non-negative f32.

    This is the conservative-update write primitive: the caller raises row
    cells with ``row += relu(M - window_read_dense)`` so a cell only grows
    to the largest single-key target that maps to it, not the sum
    (SURVEY.md §7.4 hard part #3).

    Mechanics: two-key sort puts each column's entries immediately after
    their cell, largest value first; the element *after* a cell is therefore
    its column max (or the next cell, when the column is empty); an unmix
    sort lands those per-cell picks back in dense column order.
    """
    B = col.shape[0]
    if not _use_sortmerge(B, w):
        return jnp.zeros((w,), val.dtype).at[col].max(val)
    key = _mix_keys(col, w)
    negv = jnp.concatenate([jnp.zeros((w,), val.dtype), -val])
    s_key, s_negv = jax.lax.sort((key, negv), num_keys=2, is_stable=False)
    is_batch = (s_key & 1) == 1
    first = is_batch & jnp.concatenate(
        [jnp.ones((1,), bool), ~is_batch[:-1]])   # first batch entry of a run
    contrib = jnp.where(first, -s_negv, 0.0)
    after = jnp.concatenate([contrib[1:], jnp.zeros((1,), val.dtype)])
    is_cell = ~is_batch
    ukey = jnp.where(is_cell, s_key >> 1, w + jax.lax.iota(jnp.int32, w + B))
    _, u_after = jax.lax.sort((ukey, after), num_keys=1, is_stable=False)
    return u_after[:w]
