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
from ratelimiter_tpu.ops.scans import cumsum_fast, exact_cumsum_i32


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
    row (six 32-bit gathers / scatter-maxes at 7.6 ns) on 126 us of fixed
    cost. Since PR 36 that is the per-row body's figure only: from 8,192
    rows up the direct side reads and writes once a RUN of equal keys
    (``_use_run_dedup`` below), so what it pays is 45 ns a run, and a
    skewed dispatch of 64K rows is a 1.5-1.7 ms step. The sort-merge side
    and the crossover have not been run since July (no cell has
    B >= w/2); against the run-merged direct side it moves up under skew.
    CPU/GPU backends have native gather/scatter — always direct there."""
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


# ------------------------------------------- one access a run of equal keys
#
# What the chip charges for a gather or a scatter is the INDEX (~7.6 ns
# each, serialized), whatever is indexed, and under a skewed key
# distribution most of a large dispatch's indices are repeats: rows of one
# key carry the same (h1, h2), hence the same columns. The windowed step
# (ops/sketch_kernels._sketch_step) already sorts the batch by h1 to
# sequence same-key rows; on the programs ``_use_run_dedup`` names it
# makes that sort FIRST and reads and writes the table once per *run* of
# equal (h1, h2) in that order. The helpers below are the scatter-free
# plumbing: run heads brought to the front by a sort on unique keys, a
# value spread from the heads to their rows by a sort back and an exact
# int32 cumsum of bit-pattern deltas, and each run's maximum or sum
# brought to its head. The accesses themselves are loops over fixed
# chunks of ``run_chunk(B)`` heads with a data-dependent trip count
# (sketch_kernels._run_estimate / _run_write), so what a dispatch pays
# follows the number of runs it holds, not its padded size.

#: Run heads one iteration of the access loops reads or writes: a
#: sixteenth of the batch, at least 2,048 (``run_chunk``; a gather or a
#: scatter of 1,024 indices reads 67 ns a head on the chip, 43-47 from
#: 2,048 up, PR 36).
RUN_CHUNK_MIN = 2048
RUN_CHUNKS_A_BATCH = 16


def run_chunk(B: int) -> int:
    """Static chunk of the run loops for a batch of B rows. A fixed share
    of the batch bounds both what a loop can waste on its last, partly
    filled chunk (B/16 heads: 6 % of the per-row body's accesses) and
    its trip count (16, were every row its own run)."""
    return max(RUN_CHUNK_MIN, B // RUN_CHUNKS_A_BATCH)

#: Smallest padded batch whose programs carry the mechanism (see
#: _use_run_dedup).
RUN_DEDUP_MIN_BATCH = 8192


def _use_run_dedup(B: int, w: int) -> bool:
    """Static choice (trace-time), beside ``_use_sortmerge``: does the
    windowed step of a (padded) batch of B rows on a table w wide access
    the table once a run of equal keys? Read off the shape and the
    platform only. Direct indexing regime only (B < w/2: the sort-merge
    regime pays by the table, not by the index), TPU only (CPU and GPU
    gather natively), and from ``RUN_DEDUP_MIN_BATCH`` rows up: the
    mechanism adds three sorts and two scans of B to the step and takes
    6 x 7.6 ns off every row that repeats its predecessor's key.

    The chip's reading (PR 36, TPU v5 lite, the serving step at d=3,
    w=2^20, us a step: per-row body -> run-merged under the
    configurations' own Zipf(1.1) over 262,144 keys / with every row its
    own key): pad 8,192 826 -> 657 / 832; 16,384 1,192 -> 786 / 1,243;
    32,768 1,930 -> 1,045 / 2,051; 65,536 3,414 -> 1,475 / 3,602;
    131,072 6,573 -> 2,578 / 6,718. Under the skew the run-merged step
    wins at every pad read, by 20 % at 8,192 and 57 % at 65,536, so the
    crossover lies below the smallest pad that no 4,096-batch cell
    launches and the threshold is that pad: every program of a
    4,096-row dispatch stays the per-row one. What it can lose is
    bounded by the all-distinct column: +0.8 % at 8,192, +4.5 % at
    16,384, +6.4 % at 32,768, +5.5 % at 65,536, +2.2 % at 131,072 (with
    chunks of 1,024 heads the first two read +23 % and +9 %: hence
    ``RUN_CHUNK_MIN``) — so there is no run-time arm back to the per-row
    body, which would double these programs for a case no deployment's
    keys present."""
    return (on_tpu() and B >= RUN_DEDUP_MIN_BATCH
            and not _use_sortmerge(B, w))


def run_heads(seg_head: jnp.ndarray, s_h2: jnp.ndarray) -> jnp.ndarray:
    """bool[B]: the sorted position starts a run of equal (h1, h2) — it
    opens an h1 segment (``seg_head``) or its h2 differs from its
    predecessor's. Cut on the PAIR although the order is by h1 alone:
    two keys that share h1 interleave as the batch had them and every
    switch between them opens a run with that key's own columns."""
    return seg_head | jnp.concatenate(
        [jnp.ones((1,), bool), s_h2[1:] != s_h2[:-1]])


@jax.named_scope("run_compact")
def heads_first(head: jnp.ndarray, *payload: jnp.ndarray):
    """``(key, *payload)`` with the run heads' entries first, in sorted
    order, then everyone else's: ONE sort on unique keys (a head's
    position, B + position for the rest — ``nonzero`` or a scatter of B
    indices would put 7 ns a row back). ``key`` is what ``spread_heads``
    needs to undo it."""
    B = head.shape[0]
    pos = jax.lax.iota(jnp.int32, B)
    key = jnp.where(head, pos, pos + B)
    return jax.lax.sort((key, *payload), num_keys=1, is_stable=False)


@jax.named_scope("run_spread")
def spread_heads(key: jnp.ndarray, vals: jnp.ndarray,
                 count: jnp.ndarray) -> jnp.ndarray:
    """f32[B] in sorted order: every row gets the value of its run's head.
    ``vals`` holds the heads' values in ``heads_first`` order (valid
    below ``count``). No gather of B indices: the int32 bit patterns are
    delta-encoded along the heads, one sort on ``key`` puts each delta on
    its head's position (zeros elsewhere) and an exact int32 cumsum
    telescopes them back — any bit pattern comes back as it was."""
    B = key.shape[0]
    bits = jax.lax.bitcast_convert_type(vals[:B], jnp.int32)
    k = jax.lax.iota(jnp.int32, B)
    delta = jnp.where(
        k < count, bits - jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                           bits[:-1]]), 0)
    pos = jnp.where(key >= B, key - B, key)
    _, d_s = jax.lax.sort((pos, delta), num_keys=1, is_stable=False)
    return jax.lax.bitcast_convert_type(exact_cumsum_i32(d_s), jnp.float32)


@jax.named_scope("run_max")
def run_max_at_head(vals: jnp.ndarray, head: jnp.ndarray) -> jnp.ndarray:
    """f32[B]: at each run head, the maximum of ``vals`` (>= 0) over its
    run; other positions hold a suffix maximum nobody reads. A backward
    segmented scan in log2(B) shift-and-select steps — max has no
    inverse, so the cumsum trick of the sums does not apply."""
    B = vals.shape[0]
    v = vals
    # done[i]: the window [i, i + s) already reaches its run's last row.
    done = jnp.concatenate([head[1:], jnp.ones((1,), bool)])
    s = 1
    while s < B:
        v_far = jnp.concatenate([v[s:], jnp.zeros((s,), v.dtype)])
        done_far = jnp.concatenate([done[s:], jnp.ones((s,), bool)])
        v = jnp.where(done, v, jnp.maximum(v, v_far))
        done = done | done_far
        s *= 2
    return v


def run_maxima_first(vals: jnp.ndarray, head: jnp.ndarray,
                     count: jnp.ndarray) -> jnp.ndarray:
    """f32[B]: each run's maximum of ``vals`` (>= 0) in ``heads_first``
    order, zero from ``count`` on — ``run_sums_first``'s twin."""
    _, top = heads_first(head, run_max_at_head(vals, head))
    return jnp.where(jax.lax.iota(jnp.int32, top.shape[0]) < count, top, 0.0)


@jax.named_scope("run_sum")
def run_sums_first(add: jnp.ndarray, head: jnp.ndarray,
                   count: jnp.ndarray) -> jnp.ndarray:
    """int32[B]: each run's sum of ``add`` in ``heads_first`` order, zero
    from ``count`` on. The exclusive prefix sum at a run's head is the
    mass before the run; compacted, consecutive differences are the
    runs' sums (integer sums regroup exactly)."""
    B = add.shape[0]
    c = exact_cumsum_i32(add)
    _, before = heads_first(head, c - add)
    k = jax.lax.iota(jnp.int32, B)
    after = jnp.concatenate([before[1:], c[-1:]])
    after = jnp.where(k == count - 1, c[-1], after)
    return jnp.where(k < count, after - before, 0)
