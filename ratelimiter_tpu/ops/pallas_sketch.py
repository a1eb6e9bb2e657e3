"""Fused Pallas TPU kernels for the sketch hot loop (ADR-011).

The jnp/XLA reference path (ops/sketch_kernels.py, ops/bucket_kernels.py)
expresses one decision step as ~10 separate HLO ops: materialize the
(B, d) column matrix, densify the boundary-weighted combine into a full
(d, w) f32 table, gather per row, min-fold, then scatter the write
histograms through another (d, w) round trip. On TPU each of those ops is
a kernel launch and an HBM materialization. The kernels here fuse each
half of the table access into ONE Pallas kernel gridded over the sketch
rows:

* ``window_estimate``  — column derivation (Kirsch-Mitzenmacher, in
  kernel), boundary sub-window weighting (the rollover-boundary combine),
  gather, and the min-over-rows fold, with nothing but the (B,) estimate
  leaving the kernel;
* ``cu_update`` / ``add_update`` — column derivation, per-column
  conservative-update segment max (or vanilla histogram), dense window
  read, delta clamp, and the in-place totals/cur adds, with the state
  slabs aliased in place (``input_output_aliases``);
* ``bucket_estimate`` / ``bucket_update`` — the token-bucket (GCRA debt
  meter) variants: scalar decay applied on the fly, no decayed slab ever
  materialized.

Contract (tier-1 enforced, tests/test_pallas_parity.py): decisions,
remaining, retry and reset from these kernels are BIT-IDENTICAL to the
jnp reference. That holds by construction — every float op runs in the
same order on the same values as the reference (the scatter max/add
reorderings are exact: f32 max over non-negative finite values and
integer adds are order-insensitive) — and it is what lets ``kernels=`` be
a pure execution knob (excluded from the checkpoint fingerprint).

The batch-sequencing core (ops/segment.admit) is deliberately NOT inside
the kernels: it is sort-based (multi-operand ``lax.sort`` has no Mosaic
lowering), already TPU-shaped, and SHARED with the reference path — which
is also how bit-identity of the decision logic is maintained. The fused
kernels bracket it: fused read -> admit -> fused write.

Backend handling: off-TPU every kernel runs in Pallas interpret mode
(bit-identical, slow) — the CPU parity lane, which is all these kernels
have ever run as. On a TPU nothing reaches them: Mosaic refuses all five
(MOSAIC_REFUSAL), so ``auto`` resolves to the jnp path on every platform
and ``kernels="pallas"`` is rejected at config resolution
(resolve_kernels). Kernels Mosaic accepts are ROADMAP S3/D5.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ratelimiter_tpu.core.errors import InvalidConfigError
from ratelimiter_tpu.core.jaxcfg import on_tpu

#: Why no configuration reaches these kernels on a TPU: what the Pallas
#: TPU lowering of jax 0.9.0 answers when they are lowered with
#: interpret=False (tests/test_pallas_parity.py re-checks the first refusal).
MOSAIC_REFUSAL = (
    "the Pallas TPU lowering refuses the fused sketch kernels: their "
    "(1, w) row blocks over a (d, w) table ('the last two dimensions of "
    "your block shape [must be] divisible by 8 and 128'), the in-kernel "
    "1-D gather ('Only 2D gather is supported'), scatter-max/scatter-add "
    "('Unimplemented primitive in Pallas TPU lowering') and the bucket "
    "kernels' int64 debt vectors")

#: Debt-cell clamp, mirrored from ops/bucket_kernels._DEBT_CAP (importing
#: it would be circular: bucket_kernels imports this module).
_DEBT_CAP = 1 << 61


def _interpret() -> bool:
    """Interpret mode off-TPU: same numerics, no Mosaic requirement."""
    return not on_tpu()


def resolve_kernels(cfg) -> str:
    """Resolve cfg.sketch.kernels to a concrete choice ("pallas"|"jnp").

    auto: the jnp path on every platform — no fused kernel has been
    compiled by Mosaic, let alone raced on a chip, so none is selected
    (a kernel that earns a regime gets it here, from what the code can
    observe: platform, batch, width). "pallas" off-TPU is the interpret
    parity lane; on a TPU it raises with the compiler's reason instead
    of a lowering traceback at the first dispatch, and never runs
    interpreted or gives way to the reference silently. Forcing "pallas"
    with hh_slots raises — the side table's private-cell reads are not
    fused (ADR-011 §limits).
    """
    if cfg.sketch.kernels != "pallas":
        return "jnp"
    if cfg.sketch.hh_slots:
        raise InvalidConfigError(
            "kernels='pallas' does not support the heavy-hitter side "
            "table (hh_slots > 0); use kernels='jnp' for hh configs")
    if on_tpu():
        raise InvalidConfigError(
            f"kernels='pallas' cannot serve on a TPU: {MOSAIC_REFUSAL}. "
            f"Use kernels='auto' or 'jnp'; off-TPU 'pallas' runs the "
            f"interpret-mode parity lane")
    return "pallas"


def _cols_for_row(h1, h2, r, w: int):
    """Row r's CMS columns, derived IN KERNEL from the two hash halves —
    the (B, d) column matrix never exists in HBM on the fused path.
    Bit-identical to sketch_kernels._columns row r."""
    cols = (h1 + r.astype(jnp.uint32) * h2) & jnp.uint32(w - 1)
    return cols.astype(jnp.int32)


# ------------------------------------------------------ windowed sketch


def _window_estimate_kernel(frac_ref, h1_ref, h2_ref, totals_ref,
                            boundary_ref, est_ref, *, w: int):
    r = pl.program_id(0)
    cols = _cols_for_row(h1_ref[0, :], h2_ref[0, :], r, w)
    # Dense boundary-weighted combine for THIS row, then gather: the same
    # dense-combine-then-gather order as the reference's direct-indexing
    # regime (numerically identical to its sort-merge regime too — both
    # compute totals[c] + frac * boundary[c] elementwise).
    combined = (totals_ref[0, :].astype(jnp.float32)
                + frac_ref[0, 0] * boundary_ref[0, :].astype(jnp.float32))
    e_r = combined[cols]
    # Sequential grid => the min folds in row order, exactly like the
    # reference's est = min(min(e_0, e_1), ...) chain.

    @pl.when(r == 0)
    def _():
        est_ref[0, :] = e_r

    @pl.when(r != 0)
    def _():
        est_ref[0, :] = jnp.minimum(est_ref[0, :], e_r)


def window_estimate(totals, boundary, frac, h1, h2):
    """Fused min-estimate over the d rows: (B,) f32, NOT yet clamped at 0
    (the caller applies the same jnp.maximum(est, 0.0) as the reference).
    ``boundary`` must be a (d, w) slab (zeros + frac=0 for fixed-window
    semantics — t + 0.0*b == t bitwise for int-cast t)."""
    d, w = totals.shape
    B = h1.shape[0]
    est = pl.pallas_call(
        partial(_window_estimate_kernel, w=w),
        grid=(d,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((1, B), lambda r: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.float32),
        interpret=_interpret(),
    )(jnp.asarray(frac, jnp.float32).reshape(1, 1),
      h1.reshape(1, B), h2.reshape(1, B), totals, boundary)
    return est[0]


def _cu_update_kernel(frac_ref, h1_ref, h2_ref, target_ref, totals_ref,
                      boundary_ref, cur_ref, out_totals_ref, out_cur_ref,
                      *, w: int):
    r = pl.program_id(0)
    cols = _cols_for_row(h1_ref[0, :], h2_ref[0, :], r, w)
    t_row = totals_ref[0, :]
    # Per-column segment max of the post-batch targets (f32 max over
    # non-negative values: order-insensitive, so the scatter equals the
    # reference's row_histogram_max bitwise).
    m = jnp.zeros((w,), jnp.float32).at[cols].max(target_ref[0, :])
    read = (t_row.astype(jnp.float32)
            + frac_ref[0, 0] * boundary_ref[0, :].astype(jnp.float32))
    delta = jnp.ceil(jnp.maximum(m - read, 0.0)).astype(jnp.int32)
    out_totals_ref[0, :] = t_row + delta
    out_cur_ref[0, :] = cur_ref[0, :] + delta


def cu_update(totals, cur, boundary, frac, h1, h2, target):
    """Fused conservative update: returns (new_totals, new_cur), the
    state slabs aliased in place. ``target`` is the (B,) post-batch
    per-key target (0 for denied requests) the reference computes."""
    d, w = totals.shape
    B = h1.shape[0]
    return pl.pallas_call(
        partial(_cu_update_kernel, w=w),
        grid=(d,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
        ],
        out_specs=(pl.BlockSpec((1, w), lambda r: (r, 0)),
                   pl.BlockSpec((1, w), lambda r: (r, 0))),
        out_shape=(jax.ShapeDtypeStruct((d, w), totals.dtype),
                   jax.ShapeDtypeStruct((d, w), cur.dtype)),
        input_output_aliases={4: 0, 6: 1},
        interpret=_interpret(),
    )(jnp.asarray(frac, jnp.float32).reshape(1, 1),
      h1.reshape(1, B), h2.reshape(1, B), target.reshape(1, B),
      totals, boundary, cur)


def _add_update_kernel(h1_ref, h2_ref, add_ref, totals_ref, cur_ref,
                       out_totals_ref, out_cur_ref, *, w: int):
    r = pl.program_id(0)
    cols = _cols_for_row(h1_ref[0, :], h2_ref[0, :], r, w)
    h = jnp.zeros((w,), add_ref.dtype).at[cols].add(add_ref[0, :])
    out_totals_ref[0, :] = totals_ref[0, :] + h
    out_cur_ref[0, :] = cur_ref[0, :] + h


def add_update(totals, cur, h1, h2, add):
    """Fused vanilla (sum) update: integer scatter-add per row, state
    slabs aliased in place. Exact — integer adds commute."""
    d, w = totals.shape
    B = h1.shape[0]
    return pl.pallas_call(
        partial(_add_update_kernel, w=w),
        grid=(d,),
        in_specs=[
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
        ],
        out_specs=(pl.BlockSpec((1, w), lambda r: (r, 0)),
                   pl.BlockSpec((1, w), lambda r: (r, 0))),
        out_shape=(jax.ShapeDtypeStruct((d, w), totals.dtype),
                   jax.ShapeDtypeStruct((d, w), cur.dtype)),
        input_output_aliases={3: 0, 4: 1},
        interpret=_interpret(),
    )(h1.reshape(1, B), h2.reshape(1, B), add.reshape(1, B), totals, cur)


# -------------------------------------------------------- token bucket


def _bucket_estimate_kernel(decay_ref, h1_ref, h2_ref, debt_ref, est_ref,
                            *, w: int):
    r = pl.program_id(0)
    cols = _cols_for_row(h1_ref[0, :], h2_ref[0, :], r, w)
    # Scalar decay applied on the fly — the decayed (d, w) slab is never
    # materialized (the reference materializes it; clamp-then-gather is
    # exact integer math either way).
    decayed = jnp.maximum(jnp.int64(0), debt_ref[0, :] - decay_ref[0, 0])
    e_r = decayed[cols]

    @pl.when(r == 0)
    def _():
        est_ref[0, :] = e_r

    @pl.when(r != 0)
    def _():
        est_ref[0, :] = jnp.minimum(est_ref[0, :], e_r)


def bucket_estimate(debt, decay, h1, h2):
    """Fused min-over-rows debt estimate, (B,) int64 micro-tokens."""
    d, w = debt.shape
    B = h1.shape[0]
    est = pl.pallas_call(
        partial(_bucket_estimate_kernel, w=w),
        grid=(d,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((1, B), lambda r: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int64),
        interpret=_interpret(),
    )(jnp.asarray(decay, jnp.int64).reshape(1, 1),
      h1.reshape(1, B), h2.reshape(1, B), debt)
    return est[0]


def _bucket_update_kernel(decay_ref, h1_ref, h2_ref, consumed_ref,
                          debt_ref, acc_ref, out_debt_ref, out_acc_ref,
                          *, w: int):
    r = pl.program_id(0)
    cols = _cols_for_row(h1_ref[0, :], h2_ref[0, :], r, w)
    decayed = jnp.maximum(jnp.int64(0), debt_ref[0, :] - decay_ref[0, 0])
    h = jnp.zeros((w,), jnp.int64).at[cols].add(consumed_ref[0, :])
    out_debt_ref[0, :] = jnp.minimum(decayed + h, _DEBT_CAP)
    out_acc_ref[0, :] = jnp.minimum(acc_ref[0, :] + h, _DEBT_CAP)


def bucket_update(debt, acc, decay, h1, h2, consumed):
    """Fused decay + consume: returns (new_debt, new_acc), slabs aliased
    in place. ``consumed`` is admit's (B,) int64 micro-token consumption
    (0 for denied requests — denial consumes nothing)."""
    d, w = debt.shape
    B = h1.shape[0]
    return pl.pallas_call(
        partial(_bucket_update_kernel, w=w),
        grid=(d,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, B), lambda r: (0, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
            pl.BlockSpec((1, w), lambda r: (r, 0)),
        ],
        out_specs=(pl.BlockSpec((1, w), lambda r: (r, 0)),
                   pl.BlockSpec((1, w), lambda r: (r, 0))),
        out_shape=(jax.ShapeDtypeStruct((d, w), debt.dtype),
                   jax.ShapeDtypeStruct((d, w), acc.dtype)),
        input_output_aliases={4: 0, 5: 1},
        interpret=_interpret(),
    )(jnp.asarray(decay, jnp.int64).reshape(1, 1),
      h1.reshape(1, B), h2.reshape(1, B), consumed.reshape(1, B),
      debt, acc)
