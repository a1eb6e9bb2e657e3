"""The sketched token bucket's write-back as ``_bucket_step`` wrote it
inline until PR 33 — one int64 scatter-add of micro-token amounts a
sketch row. The reference that ``bucket_kernels._debt_histograms`` (the
same cell sums from 32-bit limbs of whole tokens, widened densely, PR 34)
is held to, bit for bit, in tests/test_bucket_writeback.py and
tests/test_finished_step.py."""


def int64_histograms(cols, tokens, w):
    import jax.numpy as jnp

    from ratelimiter_tpu.core.clock import MICROS
    from ratelimiter_tpu.ops.sortmerge import row_histogram

    consumed = tokens.astype(jnp.int64) * MICROS
    return jnp.stack([row_histogram(cols[:, r], consumed, w)
                      for r in range(cols.shape[1])])
