"""The per-key override lookup as the sketch steps wrote it inline until
PR 30 — unconditional, whatever the table holds. The reference that
``policy_kernels.limit_for_rows`` (the same lines under a branch on the
table's occupancy, PR 31) is held to, bit for bit, in tests/test_policy.py
and tests/test_finished_step.py."""


def inline_lookup(policy, h1, h2, default, dtype):
    import jax.numpy as jnp

    from ratelimiter_tpu.ops import policy_kernels as pk

    q = pk.pack_halves(h1, h2)
    pidx, pfound = pk.lookup_i64(policy["key"], q)
    return jnp.where(pfound, policy["limit"][pidx],
                     jnp.int64(default)).astype(dtype)
