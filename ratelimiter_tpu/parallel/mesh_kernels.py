"""shard_map'd sketch step kernels for the chip mesh.

State is fully replicated (every chip holds the identical sketch); the
request batch is sharded over the mesh axis. The two merge modes and their
collectives:

* gather: one ``all_gather`` of the (h1, h2, n) shards -> every chip runs
  the rule's step body (sketch_kernels._sketch_step or
  bucket_kernels._bucket_step) on the full global batch and slices out
  its own shard's verdicts. The state update is a replicated
  deterministic computation — no further collective. Global request order is
  chip-major (chip 0's shard first), the batched analog of Redis serializing
  whichever client's EVAL lands first (SURVEY.md §3.1).
* delta: the body with ``axis_name=...`` — local admission against the
  replicated counts, one ``psum`` of the write histograms (always vanilla
  update: cross-chip counts must add — see _sketch_step's CU note). The
  merged delta is identical on every chip, so replication is preserved by
  construction. The bucket's scalar decay is a deterministic function of
  replicated (rem, last) and needs no collective.

Rollover and reset are replicated computations on replicated state — plain
jit, no collective, no shard_map: the single-chip controls
(sketch_kernels.build_controls / bucket_kernels.build_controls) serve
the mesh as they are, and no builder here returns them.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.ops import memoized, sketch_kernels
from ratelimiter_tpu.parallel.mesh import AXIS

MERGE_MODES = ("gather", "delta")

#: Replicated cascade-table spec (key→tenant map + limit/weight columns,
#: ADR-020) — appended to in_specs only when the hierarchy is enabled so
#: disabled configs keep their exact pre-hierarchy call shape.
_HIER_SPEC = {"key": P(), "tid": P(), "limit": P(), "weight": P()}


def _gather_step(step, state, h1, h2, n, now_us, policy, hier, *, step_kw):
    """Gather-mode per-chip body: all_gather shards, decide globally with
    the rule's ``step``, slice local verdicts. The policy (and cascade)
    tables are replicated like the state."""
    Bl = h1.shape[0]
    h1g = jax.lax.all_gather(h1, AXIS).reshape(-1)
    h2g = jax.lax.all_gather(h2, AXIS).reshape(-1)
    ng = jax.lax.all_gather(n, AXIS).reshape(-1)
    state, outs = step(state, h1g, h2g, ng, now_us, policy, hier, **step_kw)
    i = jax.lax.axis_index(AXIS)
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * Bl, Bl)
    return state, tuple(sl(x) for x in outs)


def _delta_step(step, state, h1, h2, n, now_us, policy, hier, *, step_kw):
    """Delta-mode per-chip body: local decide, collective-merged write
    (the cascade's tenant histogram psums alongside the table's — same
    bounded-staleness contract)."""
    return step(state, h1, h2, n, now_us, policy, hier, axis_name=AXIS,
                **step_kw)


# ------------------------------------------------------ the serving step
#
# The mesh twin of the single-chip serving step (ADR-011), for either
# rule: the batch shards carry ONE uint64 per key and one per ``n``, the
# (h1, h2) split — plus, with premix, the splitmix64 finalizer — runs
# inside the shard_map'd body (elementwise, so sharding is preserved with
# no extra collective), and the body ends with the rule's own packing of
# its shard's verdicts (one int32 buffer a device). Which body, which
# statics, which state leaves and which packer are read off the config
# in ops/route_kernels (step_rule, state_layout), the same table the
# collective router's step is built from. The operands stay three
# arrays here: the mesh placement stages the single-chip slot's three
# views itself, the timestamp replicated (_MeshPlacement._stage_operands;
# the collective router's launch repeats its scalars in each device's row
# of ONE sharded buffer instead, sketch_kernels.unstage_routed).

_BUILT: Dict[tuple, Callable] = {}


def _hashed_body(body, step, pack, seed: int, premix: bool, step_kw,
                 hier_arity: bool = False):
    """Per-chip body over the staged views: ``n`` and ``now_us`` arrive
    as uint64 (the slot's dtype) and narrow here; ``pack(allowed,
    remaining, third)`` is the rule's result packing."""
    def decide(state, h64, n, now_us, policy, hier):
        h1, h2 = sketch_kernels.split_staged(h64, premix, seed)
        now_us = now_us.astype(jnp.int64)
        state, outs = body(step, state, h1, h2, n.astype(jnp.int32), now_us,
                           policy, hier, step_kw=step_kw)
        return state, pack(*outs)

    if hier_arity:
        return decide
    return lambda state, h64, n, now_us, policy: decide(
        state, h64, n, now_us, policy, None)


def build_mesh_hashed_step(cfg: Config, mesh: Mesh, merge: str = "gather",
                           *, premix: bool = False) -> Callable:
    """Jitted mesh ``step(state, h64, n, now_us, policy[, hier])`` for
    ``cfg``'s rule — h64/n sharded over AXIS (length divisible by mesh
    size), state, now_us and the policy (and cascade) tables replicated
    — returning ``(state, the rule's packed buffer)``, each device's
    shard that buffer over its own keys, and the state replicated."""
    from ratelimiter_tpu.ops import route_kernels

    if merge not in MERGE_MODES:
        raise ValueError(f"merge must be one of {MERGE_MODES}, got {merge!r}")
    step, step_kw, pack = route_kernels.step_rule(cfg)
    _, mut_keys, ro_keys = route_kernels.state_layout(cfg)
    seed = cfg.sketch.seed
    # Key on the mesh's *identity-bearing contents* (device objects + axis
    # names), not id(mesh): a GC'd mesh's id can be reused by a new mesh,
    # which would receive a stale compiled step bound to dead devices.
    mesh_key = (tuple(mesh.devices.flat), mesh.axis_names)

    def build():
        # State (the sketch, the cascade's counter slab, the hh side
        # table) is replicated: gather mode updates it with a replicated
        # computation; delta mode psums the write histograms and pmaxes
        # the promotion claims (_sketch_step).
        state_spec = {k: P() for k in mut_keys + ro_keys}
        policy_spec = {"key": P(), "limit": P()}  # replicated override table
        in_specs = [state_spec, P(AXIS), P(AXIS), P(), policy_spec]
        if step_kw["tenants"]:
            in_specs.append(_HIER_SPEC)
        # check_vma=False: the state outputs ARE replicated — they are a
        # deterministic function of replicated state and all_gathered/
        # psum'd batch data — but the static checker cannot prove that
        # through lax.sort/cumsum chains. tests/test_multichip.py asserts
        # the replication invariant behaviorally (mesh result ==
        # single-chip).
        mapped = shard_map(
            _hashed_body(_gather_step if merge == "gather" else _delta_step,
                         step, pack, seed, premix, step_kw,
                         hier_arity=bool(step_kw["tenants"])),
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(state_spec, P(AXIS)),
            check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=(0,))

    return memoized(_BUILT, step_kw, (mesh_key, merge, seed, premix), build)


def replicate_state(state, mesh: Mesh):
    """Place a (host or single-device) state dict fully replicated on the mesh."""
    sh = NamedSharding(mesh, P())
    return {k: jax.device_put(v, sh) for k, v in state.items()}
