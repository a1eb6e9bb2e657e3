"""Device-side all-to-all frame routing for the sliced mesh (ADR-024).

The host router (ADR-013) partitions every mixed frame on the host — a
stable argsort over the owner vector, per-slice sub-launches, a barrier,
and an index-map scatter of results. This module is the SPMD answer the
ADR deferred: one shard_map'd step over the slice mesh in which each
device

1. receives its row of the ONE staged operand — an even 1/n shard of the
   frame's (h64, ns) columns, the decision timestamp and the frame's row
   count (sketch_kernels.unstage_routed),
2. computes ``owner = h64 % n`` on device (premix lanes splitmix64
   first — the same finalize-then-mod rule as
   ``SlicedMeshLimiter.owner_of_id``),
3. bins its rows into fixed-capacity per-destination bins and routes
   them with ONE ``jax.lax.all_to_all``,
4. runs the UNCHANGED fused decision kernel
   (sketch_kernels._sketch_step / bucket_kernels._bucket_step) on the
   rows it owns, against its own slice state (sharded, not replicated —
   each device's shard IS that slice's counters), and
5. all-to-all's the verdicts back to source order and packs them, in
   frame order, into the rule's one int32 result buffer
   (sketch_kernels.pack_window / bucket_kernels.pack_bucket) with a tail
   of its own: this slice's admitted mass and the overflow flag.

The host never argsorts, never builds index maps, never fans out
sub-launches; resolve blocks on one ticket.

Bit-identity with the host-routed oracle holds because the destination
device runs the exact same step body on the exact same rows in the exact
same order: a source shard is a contiguous chunk of the frame, bins fill
in shard order, and the tiled all_to_all concatenates source-major — so
an owner's received rows are in global frame order, which is precisely
the order the host router's stable argsort feeds that slice. Pad rows
(key 0, n = 0) are decision-inert in both paths (no mass, no counter
write), so differing pad counts cannot diverge state.

Bins are fixed capacity C per (source, destination) pair — shapes must
be static under jit. A source with more than C rows for one destination
sets a device-computed overflow flag (pmax'd to every device); the step
then keeps ALL state leaves untouched (``jnp.where(ovf, old, new)``)
and the host re-dispatches the frame through the host router, so
admission is never silently dropped OR double-counted. Capacity is
``ceil(bin_headroom * L / n)`` (MeshSpec.bin_headroom): uniform mixed
traffic expects L/n rows per bin, affine single-owner frames need up to
L and deliberately overflow to the host router's single-owner
passthrough instead of paying n× bin memory (the trade-off recorded in
docs/ADR/024-collective-mesh-router.md).
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.parallel.mesh import AXIS

#: Words each device appends to its shard of the packed result: admitted
#: mass (low, high), overflow flag.
ROUTED_TAIL = 3

#: Empty bin slots travel with this ns sentinel so the destination can
#: tell a routed row from bin padding without shipping an index column.
_EMPTY = -1


def bin_capacity(L: int, n: int, headroom: float) -> int:
    """Static per-(source, destination) bin capacity for an L-row shard
    on an n-device mesh. Clamped to [1, L]: a source can send at most
    its whole shard to one destination, and zero-capacity bins would
    overflow every non-empty frame. Two lower bounds apply on top of
    the headroom multiplier when headroom >= 1 (headroom < 1 skips
    both so tests can force capacity-1 bins to exercise the fallback):

    * a flat floor of 8 rows (the _MIN_PAD instinct) so SMALL mixed
      frames — where binomial noise dwarfs the L/n mean — do not
      overflow constantly, and
    * a binomial tail bound ``mean + 4*sqrt(mean) + 8``: each of the
      n^2 (source, destination) pairs receives Bin(L, 1/n) rows, and a
      plain 2x-mean headroom still overflows ~10-20% of uniform frames
      at mid sizes (L=32, n=8 puts C at 8 against a mean of 4 —
      measured maxbin 9-10). Four sigmas plus slack pushes per-frame
      overflow below ~1e-4 while the bin memory stays O(L) per device.
    """
    c = int(-(-int(headroom * L) // n)) if headroom > 0 else 1
    if headroom >= 1.0:
        mean = L / n
        tail = int(mean + 4.0 * mean ** 0.5 + 8)
        c = max(c, 8, tail)
    return max(1, min(L, c))


def _route(h64, ns, b, n: int, L: int, C: int, premix: bool):
    """Per-device routing prologue: owner mod, per-destination ranks,
    bin scatter, one all_to_all each for the key and count columns.
    Returns (h_own, ns_own, order, binpos, keep, ovf_local) where
    h_own/ns_own are the owned rows compacted to the front in global
    frame order and padded with decision-inert (0, 0) rows."""
    from ratelimiter_tpu.ops.hashing import splitmix64_dev

    # The phases carry jax.named_scope's (owners, bin, exchange_out,
    # unbin; the body adds decide, exchange_back) into each op's op_name,
    # so a device trace can split the exchange from the decision kernel
    # it wraps. "bin" is rows -> bin layout and "unbin" bin layout ->
    # rows, on whichever side of an exchange they run.
    with jax.named_scope("owners"):
        me = jax.lax.axis_index(AXIS)
        gidx = me.astype(jnp.int64) * L + jnp.arange(L, dtype=jnp.int64)
        valid_src = gidx < b
        hfin = splitmix64_dev(h64) if premix else h64
        owner = (hfin % jnp.uint64(n)).astype(jnp.int32)
    with jax.named_scope("bin"):
        # Exclusive per-destination rank among this shard's valid rows:
        # a one-hot cumsum (L x n) — no sort on the routing path.
        oh = ((owner[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :])
              & valid_src[:, None]).astype(jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - oh,
                                   owner[:, None].astype(jnp.int32),
                                   axis=1)[:, 0]
        keep = valid_src & (rank < C)
        ovf_local = jnp.any(valid_src & (rank >= C))
        binpos = owner * C + rank
        # Out-of-range scatter index drops the row (bin padding keeps the
        # _EMPTY sentinel) — no host-side compaction, no dynamic shapes.
        pos = jnp.where(keep, binpos, n * C)
        send_h = jnp.zeros(n * C, jnp.uint64).at[pos].set(h64, mode="drop")
        send_ns = jnp.full(n * C, _EMPTY, jnp.int32).at[pos].set(
            ns, mode="drop")
    with jax.named_scope("exchange_out"):
        recv_h = jax.lax.all_to_all(send_h, AXIS, 0, 0, tiled=True)
        recv_ns = jax.lax.all_to_all(send_ns, AXIS, 0, 0, tiled=True)
    with jax.named_scope("unbin"):
        valid_r = recv_ns != _EMPTY
        # Compact owned rows to the front. Source shards are contiguous
        # frame chunks and the tiled all_to_all concatenates source-major,
        # so a STABLE sort on validity preserves global frame order — the
        # order the host router's stable argsort would feed this slice
        # (the bit-identity linchpin: in-batch same-key sequencing).
        order = jnp.argsort(~valid_r, stable=True)
        vr = valid_r[order]
        h_own = jnp.where(vr, recv_h[order], jnp.uint64(0))
        ns_own = jnp.where(vr, recv_ns[order], 0)
    return h_own, ns_own, order, binpos, keep, ovf_local


def _return_route(cols, order, binpos, keep):
    """Inverse-scatter per-row result columns into the bin layout and
    all_to_all them back to their source devices; gather into source row
    order. Rows the source never shipped (overflow) read slot 0 garbage
    — the frame is re-dispatched host-side in that case, so the values
    never reach a client."""
    with jax.named_scope("bin"):
        backs = [jnp.zeros(c.shape, c.dtype).at[order].set(c) for c in cols]
    with jax.named_scope("exchange_back"):
        rets = [jax.lax.all_to_all(back, AXIS, 0, 0, tiled=True)
                for back in backs]
    with jax.named_scope("unbin"):
        safe = jnp.where(keep, binpos, 0)
        return [ret[safe] for ret in rets]


def state_layout(cfg: Config) -> Tuple[str, Tuple[str, ...],
                                       Tuple[str, ...]]:
    """(kind, mutated leaves, read-only leaves) of the per-slice state
    under one routed step. Read-only leaves (the slab ring and its
    period bookkeeping — only the host-driven rollover writes them) ride
    as a second operand group that is never an output, so the step
    neither copies nor donates them."""
    from ratelimiter_tpu.core.types import Algorithm

    if cfg.algorithm is Algorithm.TOKEN_BUCKET:
        mut = ["debt", "acc", "rem", "last"]
        if cfg.hierarchy.tenants:
            mut += ["tn_counts", "tn_period"]
        return "bucket", tuple(mut), ()
    mut = ["cur", "totals"]
    ro = ["slabs", "slab_period", "last_period"]
    if cfg.hierarchy.tenants:
        mut += ["tn_cur", "tn_totals"]
        ro += ["tn_slabs"]
    if cfg.sketch.hh_slots:
        mut += ["hh_owner", "hh_owner2", "hh_cur", "hh_totals", "hh_last"]
        ro += ["hh_slabs"]
    return "sketch", tuple(mut), tuple(ro)


def step_rule(cfg: Config) -> Tuple[Callable, dict, Callable]:
    """(step body, its static keyword arguments, packer) of ``cfg``'s
    rule: with state_layout, the one table the programs that
    wrap a step body on a mesh are built from (build_routed_step below,
    parallel/mesh_kernels.build_mesh_hashed_step). ``packer(allowed,
    remaining, third)`` takes the body's three outputs; the windowed
    rules ship no third column (it is the estimate)."""
    from ratelimiter_tpu.core.types import Algorithm
    from ratelimiter_tpu.ops import bucket_kernels, sketch_kernels

    if cfg.algorithm is Algorithm.TOKEN_BUCKET:
        return (bucket_kernels._bucket_step,
                bucket_kernels.step_statics(cfg), bucket_kernels.pack_bucket)
    return (sketch_kernels._sketch_step, sketch_kernels.step_statics(cfg),
            lambda allowed, remaining, *_est:
            sketch_kernels.pack_window(allowed, remaining))


#: Per-slice state leaves that are scalars on a slice (assembled as an
#: (n,) global, local (1,) — the body unwraps/rewraps them).
_SCALAR_LEAVES = frozenset(["last_period", "rem", "last", "tn_period"])

_ROUTED_CACHE: Dict[tuple, Callable] = {}


def build_routed_step(cfg: Config, mesh, *, premix: bool, L: int,
                      capacity: int) -> Callable:
    """Jitted collective ``step(mut, ro, staged, policy[, hier])`` over
    the slice mesh.

    ``mut``/``ro`` are the sharded per-slice state groups
    (state_layout); ``staged`` is the frame's ONE operand, ``uint64[n,
    2L + 2]`` sharded by rows over AXIS: chip c's row holds frame rows
    c*L .. c*L + L - 1 as ``[h64(L) | ns(L) | now_us | b]``, the decision
    timestamp and the true row count repeated in every row
    (sketch_kernels.unstage_routed; both traced, so varying b never
    recompiles — only a new L bucket does). Policy (and cascade) tables
    ride replicated, exactly as on the single-slice step.

    Returns ``(new_mut, words)``: ONE int32 buffer sharded over AXIS,
    each device's shard the rule's packed rows over its L frame rows
    (global frame order once the shards lie side by side —
    sketch_kernels.result_rows) followed by ROUTED_TAIL words of its
    own: this slice's admitted mass as (low, high) words and the
    overflow flag (a pmax, the same on every device). On overflow every
    state leaf is returned UNCHANGED."""
    from ratelimiter_tpu.parallel.mesh_kernels import _HIER_SPEC, shard_map
    from jax.sharding import PartitionSpec as P

    from ratelimiter_tpu.ops import memoized, sketch_kernels

    n = mesh.devices.size
    step, step_kw, pack = step_rule(cfg)
    kind, mut_keys, ro_keys = state_layout(cfg)
    seed = cfg.sketch.seed
    mesh_key = (tuple(mesh.devices.flat), mesh.axis_names)

    def build():
        C = capacity

        def _unwrap(mut, ro):
            state = {}
            for k in mut_keys:
                state[k] = mut[k][0] if k in _SCALAR_LEAVES else mut[k]
            for k in ro_keys:
                state[k] = ro[k][0] if k in _SCALAR_LEAVES else ro[k]
            return state

        def _rewrap_mut(new_state, old_mut, ovf):
            out = {}
            for k in mut_keys:
                v = new_state[k]
                if k in _SCALAR_LEAVES:
                    v = v.reshape(1)
                # Overflow leaves the frame to the host router: EVERY
                # state write is suppressed so the re-dispatch admits each
                # row exactly once (no lost, no duplicated admission mass).
                out[k] = jnp.where(ovf, old_mut[k], v)
            return out

        def body(mut, ro, staged, policy, hier=None):
            h64, ns, now_us, b = sketch_kernels.unstage_routed(staged[0])
            h_own, ns_own, order, binpos, keep, ovf_l = _route(
                h64, ns, b, n, L, C, premix)
            ovf = jax.lax.pmax(ovf_l.astype(jnp.int32), AXIS) > 0
            state = _unwrap(mut, ro)
            # The step's own scopes (hash_split, estimate, admit, ...) stay
            # inside "decide".
            with jax.named_scope("decide"):
                h1, h2 = sketch_kernels.split_staged(h_own, premix, seed)
                new_state, (allowed, remaining, third) = step(
                    state, h1, h2, ns_own, now_us, policy, hier, **step_kw)
            mass = jnp.sum(jnp.where(allowed, ns_own, 0)
                           .astype(jnp.int64)).reshape(1)
            tail = (*sketch_kernels.split_words(mass), ovf.reshape(1))
            cols = [allowed.astype(jnp.uint8), remaining]
            if kind == "bucket":
                cols.append(third)  # retry_us; the windowed rules ship none
            rets = _return_route(cols, order, binpos, keep)
            with jax.named_scope("finish"):
                words = jnp.concatenate(
                    [pack(rets[0].astype(jnp.bool_), *rets[1:]),
                     sketch_kernels.pack_rows(*tail)])
            return _rewrap_mut(new_state, mut, ovf), words

        # jax.jit names the compiled module after its function: a profile
        # shows jit_routed_sketch_step / jit_routed_bucket_step, not
        # jit_body.
        body.__name__ = f"routed_{kind}_step"

        mut_spec = {k: P(AXIS) for k in mut_keys}
        ro_spec = {k: P(AXIS) for k in ro_keys}
        policy_spec = {"key": P(), "limit": P()}
        in_specs = [mut_spec, ro_spec, P(AXIS), policy_spec]
        if step_kw["tenants"]:
            in_specs.append(_HIER_SPEC)
        # check_vma=False for the same reason as mesh_kernels: ovf IS
        # replicated (a pmax result) but the checker cannot prove it, and
        # the sharded state outputs flow through sort/cumsum chains.
        mapped = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=(mut_spec, P(AXIS)), check_vma=False)
        # No donation: the assembled global state aliases the slices' own
        # pinned buffers (jax.make_array_from_single_device_arrays is
        # zero-copy), and donating would invalidate them mid-writeback.
        # The RO group (the big slab ring) is never an output, so the
        # copy cost is bounded by the small mutated leaves.
        return jax.jit(mapped)

    return memoized(_ROUTED_CACHE, step_kw,
                    (mesh_key, seed, premix, L, capacity), build)
