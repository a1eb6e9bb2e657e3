"""Contract suite instantiated for the dense device backend, plus
dense-specific behavior (slot capacity, recycling, fault injection)."""

import pytest

from tests.contract import ContractTests

from ratelimiter_tpu import (
    Algorithm,
    Config,
    DenseParams,
    ManualClock,
    StorageUnavailableError,
    create_limiter,
)


class TestDenseContract(ContractTests):
    backend = "dense"
    supports_failure_injection = True

    def inject_failure(self, lim) -> None:
        lim.inject_failure()


def make(algo=Algorithm.FIXED_WINDOW, limit=5, window=60.0, capacity=8, **kw):
    clock = ManualClock()
    cfg = Config(algorithm=algo, limit=limit, window=window,
                 dense=DenseParams(capacity=capacity), **kw)
    return create_limiter(cfg, backend="dense", clock=clock), clock


class TestDenseSlots:
    def test_capacity_exhaustion_fail_closed(self):
        lim, _ = make(capacity=2)
        lim.allow("a")
        lim.allow("b")
        with pytest.raises(StorageUnavailableError):
            lim.allow("c")
        lim.close()

    def test_capacity_exhaustion_fail_open(self):
        lim, _ = make(capacity=2, fail_open=True)
        lim.allow("a")
        lim.allow("b")
        res = lim.allow("c")
        assert res.allowed and res.fail_open
        lim.close()

    def test_prune_recycles_slots(self):
        lim, clock = make(capacity=2, window=10.0)
        lim.allow("a")
        lim.allow("b")
        clock.advance(21.0)  # 2x window -> TTL horizon
        lim.allow("c")       # forces prune of a/b instead of failing
        assert lim.key_count() == 1
        lim.close()

    def test_recycled_slot_state_is_fresh(self):
        lim, clock = make(algo=Algorithm.TOKEN_BUCKET, limit=3, capacity=1,
                          window=10.0)
        assert lim.allow_n("a", 3).allowed      # drain a's bucket
        clock.advance(21.0)
        assert lim.allow_n("b", 3).allowed      # b reuses a's slot, starts full
        lim.close()

    def test_reset_frees_slot(self):
        lim, _ = make(capacity=1)
        lim.allow("a")
        lim.reset("a")
        assert lim.allow("b").allowed  # slot available again
        lim.close()

    def test_heal_after_injected_failure(self):
        lim, _ = make(fail_open=True)
        lim.inject_failure()
        assert lim.allow("k").fail_open
        lim.heal()
        assert not lim.allow("k").fail_open
        lim.close()

    def test_large_batch_padding(self):
        lim, _ = make(capacity=64, limit=100)
        keys = [f"k{i % 50}" for i in range(100)]  # non-power-of-two batch
        out = lim.allow_batch(keys)
        assert out.allow_count == 100
        lim.close()


# ------------------------------------- no 64-bit table at a boundary

def _boundary(program, *operands):
    """(shape, dtype) of every parameter and every result of the lowered
    program, in order."""
    import jax

    lowered = program.lower(*operands)
    return [(tuple(leaf.shape), leaf.dtype) for leaf in
            jax.tree.leaves((lowered.args_info, lowered.out_info))]


def _programs(algo, capacity):
    """name -> boundary of every device program that takes the dense
    state: the serving steps of both lanes, the three control programs
    and the table-sized control updates."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ratelimiter_tpu.ops import dense_kernels

    cfg = Config(algorithm=algo, limit=5, window=60.0,
                 dense=DenseParams(capacity=capacity))
    lim = create_limiter(cfg, backend="dense", clock=ManualClock())
    with lim._lock:
        policy = lim._policy_device()
    state = lim._state
    staged = jax.ShapeDtypeStruct((2 * 64 + 1,), jnp.uint64)
    ids = jax.ShapeDtypeStruct((1,), jnp.uint64)
    valid = jax.ShapeDtypeStruct((1,), jnp.bool_)
    scalar = np.int64(0)
    flag = np.bool_(False)
    reclaim, forget, clear_rem = dense_kernels.build_controls(cfg, capacity)
    out = {
        "step": _boundary(lim._step, state, staged, policy),
        "ids_step": _boundary(lim._get_ids_step(), state, staged, policy),
        "reclaim": _boundary(reclaim, state, scalar, lim._fresh),
        "forget": _boundary(forget, state, ids, valid, lim._fresh),
        "clear_rem": _boundary(clear_rem, state, ids, valid, lim._fresh),
    }
    rewrites = {
        Algorithm.TOKEN_BUCKET: [(dense_kernels.shift_tokens, 2, 0),
                                 (dense_kernels.clear_rem, 0, 0)],
        Algorithm.FIXED_WINDOW: [(dense_kernels.rebucket_fixed, 2, 0)],
        Algorithm.SLIDING_WINDOW: [(dense_kernels.rebucket_sliding, 3, 2)],
    }[algo]
    for update, scalars, flags in rewrites:
        out[update.__name__] = _boundary(
            dense_kernels.build_rewrite(algo, update), state,
            *[scalar] * scalars, *[flag] * flags)
    lim.close()
    return out


@pytest.mark.parametrize("algo", [Algorithm.TOKEN_BUCKET,
                                  Algorithm.FIXED_WINDOW,
                                  Algorithm.SLIDING_WINDOW],
                         ids=lambda a: a.value)
def test_no_64_bit_array_sized_by_the_capacity_crosses_a_boundary(algo):
    """The TPU splits a 64-bit array where it enters a program and
    recombines it where it leaves: a table held as int64 costs every
    dispatch two passes over the whole of it (ISSUE 43: 30.7 of a
    32.2 ms step at 2^26 entries). Every program the dense state goes
    through is lowered at two capacities; a parameter or result whose
    size differs between them — one that grows with the table — must
    have 32-bit elements or narrower. The 64-bit operands that remain
    are the batch's (the staged buffer, the override table, scalars)."""
    import numpy as np

    small, large = _programs(algo, 1 << 10), _programs(algo, 1 << 13)
    assert small.keys() == large.keys() and len(small) >= 6
    for name in small:
        assert len(small[name]) == len(large[name]), name
        grew = 0
        for (s_shape, s_dtype), (l_shape, l_dtype) in zip(small[name],
                                                          large[name]):
            assert s_dtype == l_dtype, name
            if s_shape != l_shape:
                grew += 1
                assert np.dtype(l_dtype).itemsize <= 4, \
                    (name, l_shape, l_dtype)
        # cols, dir_lo, dir_hi: in (donated) and out again.
        assert grew == 6, (name, grew)
