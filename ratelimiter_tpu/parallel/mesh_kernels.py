"""shard_map'd sketch step kernels for the chip mesh.

State is fully replicated (every chip holds the identical sketch); the
request batch is sharded over the mesh axis. The two merge modes and their
collectives:

* gather: one ``all_gather`` of the (h1, h2, n) shards -> every chip runs
  ratelimiter_tpu.ops.sketch_kernels._sketch_step on the full global batch
  and slices out its own shard's verdicts. The state update is a replicated
  deterministic computation — no further collective. Global request order is
  chip-major (chip 0's shard first), the batched analog of Redis serializing
  whichever client's EVAL lands first (SURVEY.md §3.1).
* delta: ``_sketch_step(axis_name=...)`` — local admission against the
  replicated counts, one ``psum`` of the write histograms (always vanilla
  update: cross-chip counts must add — see _sketch_step's CU note). The
  merged delta is identical on every chip, so replication is preserved by
  construction.

Rollover and reset are replicated computations on replicated state — plain
jit, no collective, no shard_map (ratelimiter_tpu.ops.sketch_kernels).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ratelimiter_tpu.core.config import Config
from ratelimiter_tpu.ops import sketch_kernels
from ratelimiter_tpu.parallel.mesh import AXIS

MERGE_MODES = ("gather", "delta")

#: Replicated cascade-table spec (key→tenant map + limit/weight columns,
#: ADR-020) — appended to in_specs only when the hierarchy is enabled so
#: disabled configs keep their exact pre-hierarchy call shape.
_HIER_SPEC = {"key": P(), "tid": P(), "limit": P(), "weight": P()}


def _gather_step(state, h1, h2, n, now_us, policy, hier=None, *, step_kw):
    """Gather-mode per-chip body: all_gather shards, decide globally,
    slice local verdicts. The policy (and cascade) tables are replicated
    like the state."""
    Bl = h1.shape[0]
    h1g = jax.lax.all_gather(h1, AXIS).reshape(-1)
    h2g = jax.lax.all_gather(h2, AXIS).reshape(-1)
    ng = jax.lax.all_gather(n, AXIS).reshape(-1)
    state, (allowed, remaining, est) = sketch_kernels._sketch_step(
        state, h1g, h2g, ng, now_us, policy, hier, **step_kw)
    i = jax.lax.axis_index(AXIS)
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * Bl, Bl)
    return state, (sl(allowed), sl(remaining), sl(est))


def _delta_step(state, h1, h2, n, now_us, policy, hier=None, *, step_kw):
    """Delta-mode per-chip body: local decide, collective-merged write
    (the cascade's tenant histogram psums alongside the CMS write —
    same bounded-staleness contract)."""
    return sketch_kernels._sketch_step(
        state, h1, h2, n, now_us, policy, hier, axis_name=AXIS, **step_kw)


_MESH_CACHE: Dict[tuple, Tuple[Callable, Callable, Callable]] = {}


def build_mesh_steps(cfg: Config, mesh: Mesh, merge: str = "gather",
                     ) -> Tuple[Callable, Callable, Callable]:
    """Returns (step, reset, rollover) for the mesh.

    ``step(state, h1, h2, n, now_us, policy)`` expects h1/h2/n sharded
    over AXIS (length divisible by mesh size), state AND the policy
    override table replicated; returns sharded verdicts and replicated
    state. ``reset`` / ``rollover`` are the plain replicated kernels from
    sketch_kernels.build_steps (they run unsharded on the replicated
    state arrays).
    """
    if merge not in MERGE_MODES:
        raise ValueError(f"merge must be one of {MERGE_MODES}, got {merge!r}")
    W, sub_us, SW, S, limit = sketch_kernels.sketch_geometry(cfg)
    from ratelimiter_tpu.core.types import Algorithm

    d, w = cfg.sketch.depth, cfg.sketch.width
    weighted = cfg.algorithm is not Algorithm.FIXED_WINDOW
    cu = cfg.sketch.conservative_update
    hh, hh_thresh = sketch_kernels._hh_params(cfg)
    # Key on the mesh's *identity-bearing contents* (device objects + axis
    # names), not id(mesh): a GC'd mesh's id can be reused by a new mesh,
    # which would receive a stale compiled step bound to dead devices.
    tenants = cfg.hierarchy.tenants
    mesh_key = (tuple(mesh.devices.flat), mesh.axis_names)
    key = (mesh_key, merge, limit, W, SW, d, w,
           cfg.max_batch_admission_iters, weighted, cu, hh, hh_thresh,
           tenants)
    cached = _MESH_CACHE.get(key)
    if cached is not None:
        return cached

    step_kw = dict(limit=limit, sub_us=sub_us, SW=SW, S=S, d=d, w=w,
                   iters=cfg.max_batch_admission_iters, weighted=weighted,
                   conservative=cu, hh=hh, hh_thresh=hh_thresh,
                   tenants=tenants)
    body = _gather_step if merge == "gather" else _delta_step

    state_keys = ["cur", "slabs", "totals", "slab_period", "last_period"]
    if tenants:
        # Cascade counter slab: replicated like the sketch (gather mode
        # recomputes it deterministically; delta mode psums tn_hist).
        state_keys += ["tn_cur", "tn_slabs", "tn_totals"]
    if hh:
        # Side-table state is replicated like the sketch: gather mode
        # updates it with a replicated computation; delta mode psums the
        # write histogram and pmaxes the promotion claims (_sketch_step).
        state_keys += ["hh_owner", "hh_owner2", "hh_cur", "hh_slabs",
                       "hh_totals", "hh_last"]
    state_spec = {k: P() for k in state_keys}
    policy_spec = {"key": P(), "limit": P()}  # replicated override table
    in_specs = [state_spec, P(AXIS), P(AXIS), P(AXIS), P(), policy_spec]
    if tenants:
        in_specs.append(_HIER_SPEC)
    # check_vma=False: the state outputs ARE replicated — they are a
    # deterministic function of replicated state and all_gathered/psum'd
    # batch data — but the static checker cannot prove that through
    # lax.sort/cumsum chains. tests/test_multichip.py asserts the
    # replication invariant behaviorally (mesh result == single-chip).
    mapped = shard_map(
        partial(body, step_kw=step_kw),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(state_spec, (P(AXIS), P(AXIS), P(AXIS))),
        check_vma=False,
    )
    step = jax.jit(mapped, donate_argnums=(0,))
    _, reset, rollover = sketch_kernels.build_steps(cfg)
    _MESH_CACHE[key] = (step, reset, rollover)
    return step, reset, rollover


# ----------------------------------------------------- hashed-operand steps
#
# Mesh twins of sketch_kernels.build_hashed_step (ADR-011): the batch
# shards carry ONE uint64 per key and one per ``n``, the (h1, h2) split
# — plus, with premix, the splitmix64 finalizer — runs inside the
# shard_map'd body (elementwise, so sharding is preserved with no extra
# collective), and the body ends with the single-chip steps' own packing
# of its shard's verdicts (one int32 buffer a device). The operands stay three arrays: a buffer
# with a scalar tail cannot be sharded by batch, so the mesh placement
# stages the single-chip slot's three views itself
# (_MeshPlacement._stage_operands).

_MESH_HASHED_CACHE: Dict[tuple, Callable] = {}


def _hashed_body(body, pack, seed: int, premix: bool, step_kw,
                 hier_arity: bool = False):
    """Per-chip body over the staged views: ``n`` and ``now_us`` arrive
    as uint64 (the slot's dtype) and narrow here; ``pack(allowed,
    remaining, third)`` is the algorithm's result packing."""
    def decide(state, h64, n, now_us, policy, hier):
        h1, h2 = sketch_kernels.split_staged(h64, premix, seed)
        now_us = now_us.astype(jnp.int64)
        state, outs = body(state, h1, h2, n.astype(jnp.int32), now_us,
                           policy, hier, step_kw=step_kw)
        return state, pack(*outs)

    if hier_arity:
        return decide
    return lambda state, h64, n, now_us, policy: decide(
        state, h64, n, now_us, policy, None)


def build_mesh_hashed_step(cfg: Config, mesh: Mesh, merge: str = "gather",
                           *, premix: bool = False) -> Callable:
    """Jitted mesh ``step(state, h64, n, now_us, policy)`` — h64/n sharded
    over AXIS, state, now_us and policy replicated (build_mesh_steps'
    contract) — returning ``(state, pack_window's buffer)``, each
    device's shard that buffer over its own keys."""
    if merge not in MERGE_MODES:
        raise ValueError(f"merge must be one of {MERGE_MODES}, got {merge!r}")
    W, sub_us, SW, S, limit = sketch_kernels.sketch_geometry(cfg)
    from ratelimiter_tpu.core.types import Algorithm

    d, w = cfg.sketch.depth, cfg.sketch.width
    weighted = cfg.algorithm is not Algorithm.FIXED_WINDOW
    cu = cfg.sketch.conservative_update
    hh, hh_thresh = sketch_kernels._hh_params(cfg)
    tenants = cfg.hierarchy.tenants
    seed = cfg.sketch.seed
    mesh_key = (tuple(mesh.devices.flat), mesh.axis_names)
    key = ("sketch", mesh_key, merge, limit, W, SW, d, w,
           cfg.max_batch_admission_iters, weighted, cu, hh, hh_thresh,
           tenants, seed, premix)
    cached = _MESH_HASHED_CACHE.get(key)
    if cached is not None:
        return cached

    step_kw = dict(limit=limit, sub_us=sub_us, SW=SW, S=S, d=d, w=w,
                   iters=cfg.max_batch_admission_iters, weighted=weighted,
                   conservative=cu, hh=hh, hh_thresh=hh_thresh,
                   tenants=tenants)
    body = _gather_step if merge == "gather" else _delta_step

    state_keys = ["cur", "slabs", "totals", "slab_period", "last_period"]
    if tenants:
        state_keys += ["tn_cur", "tn_slabs", "tn_totals"]
    if hh:
        state_keys += ["hh_owner", "hh_owner2", "hh_cur", "hh_slabs",
                       "hh_totals", "hh_last"]
    state_spec = {k: P() for k in state_keys}
    policy_spec = {"key": P(), "limit": P()}
    in_specs = [state_spec, P(AXIS), P(AXIS), P(), policy_spec]
    if tenants:
        in_specs.append(_HIER_SPEC)
    mapped = shard_map(
        _hashed_body(
            body,
            lambda allowed, remaining, _est:
            sketch_kernels.pack_window(allowed, remaining),
            seed, premix, step_kw, hier_arity=bool(tenants)),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(state_spec, P(AXIS)),
        check_vma=False,
    )
    step = jax.jit(mapped, donate_argnums=(0,))
    _MESH_HASHED_CACHE[key] = step
    return step


def build_mesh_hashed_bucket_step(cfg: Config, mesh: Mesh,
                                  merge: str = "gather", *,
                                  premix: bool = False) -> Callable:
    """Bucket twin of build_mesh_hashed_step."""
    from ratelimiter_tpu.ops import bucket_kernels

    if merge not in MERGE_MODES:
        raise ValueError(f"merge must be one of {MERGE_MODES}, got {merge!r}")
    limit, num, den, d, w, iters = bucket_kernels._params(cfg)
    tenants, wus = bucket_kernels._hier_params(cfg)
    seed = cfg.sketch.seed
    mesh_key = (tuple(mesh.devices.flat), mesh.axis_names)
    key = ("bucket", mesh_key, merge, limit, num, den, d, w, iters,
           tenants, wus, seed, premix)
    cached = _MESH_HASHED_CACHE.get(key)
    if cached is not None:
        return cached

    step_kw = dict(limit=limit, rate_num=num, rate_den=den, d=d, w=w,
                   iters=iters, tenants=tenants, window_us=wus)
    body = _bucket_gather_step if merge == "gather" else _bucket_delta_step
    state_keys = ["debt", "acc", "rem", "last"]
    if tenants:
        state_keys += ["tn_counts", "tn_period"]
    state_spec = {k: P() for k in state_keys}
    policy_spec = {"key": P(), "limit": P()}
    in_specs = [state_spec, P(AXIS), P(AXIS), P(), policy_spec]
    if tenants:
        in_specs.append(_HIER_SPEC)
    mapped = shard_map(
        _hashed_body(
            body,
            bucket_kernels.pack_bucket,
            seed, premix, step_kw, hier_arity=bool(tenants)),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(state_spec, P(AXIS)),
        check_vma=False,
    )
    step = jax.jit(mapped, donate_argnums=(0,))
    _MESH_HASHED_CACHE[key] = step
    return step


# ------------------------------------------------------------ token bucket

def _bucket_gather_step(state, h1, h2, n, now_us, policy, hier=None, *,
                        step_kw):
    """Gather-mode bucket body: all_gather shards, decide globally on the
    replicated debt slab, slice local verdicts (same shape as _gather_step;
    the decided tuple is (allowed, remaining, retry_us))."""
    from ratelimiter_tpu.ops import bucket_kernels

    Bl = h1.shape[0]
    h1g = jax.lax.all_gather(h1, AXIS).reshape(-1)
    h2g = jax.lax.all_gather(h2, AXIS).reshape(-1)
    ng = jax.lax.all_gather(n, AXIS).reshape(-1)
    state, (allowed, remaining, retry_us) = bucket_kernels._bucket_step(
        state, h1g, h2g, ng, now_us, policy, hier, **step_kw)
    i = jax.lax.axis_index(AXIS)
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * Bl, Bl)
    return state, (sl(allowed), sl(remaining), sl(retry_us))


def _bucket_delta_step(state, h1, h2, n, now_us, policy, hier=None, *,
                       step_kw):
    """Delta-mode bucket body: local admission, psum'd debt increments.
    The scalar decay is a deterministic function of replicated (rem, last),
    so replication is preserved without a collective for it."""
    from ratelimiter_tpu.ops import bucket_kernels

    return bucket_kernels._bucket_step(
        state, h1, h2, n, now_us, policy, hier, axis_name=AXIS, **step_kw)


_MESH_BUCKET_CACHE: Dict[tuple, Tuple[Callable, Callable]] = {}


def build_mesh_bucket_steps(cfg: Config, mesh: Mesh, merge: str = "gather",
                            ) -> Tuple[Callable, Callable]:
    """(step, reset) for the sketched token bucket on a mesh. Same sharding
    contract as build_mesh_steps."""
    from ratelimiter_tpu.ops import bucket_kernels

    if merge not in MERGE_MODES:
        raise ValueError(f"merge must be one of {MERGE_MODES}, got {merge!r}")
    limit, num, den, d, w, iters = bucket_kernels._params(cfg)
    tenants, wus = bucket_kernels._hier_params(cfg)
    mesh_key = (tuple(mesh.devices.flat), mesh.axis_names)
    key = (mesh_key, merge, limit, num, den, d, w, iters, tenants, wus)
    cached = _MESH_BUCKET_CACHE.get(key)
    if cached is not None:
        return cached

    step_kw = dict(limit=limit, rate_num=num, rate_den=den, d=d, w=w,
                   iters=iters, tenants=tenants, window_us=wus)
    body = _bucket_gather_step if merge == "gather" else _bucket_delta_step
    state_keys = ["debt", "acc", "rem", "last"]
    if tenants:
        state_keys += ["tn_counts", "tn_period"]
    state_spec = {k: P() for k in state_keys}
    policy_spec = {"key": P(), "limit": P()}
    in_specs = [state_spec, P(AXIS), P(AXIS), P(AXIS), P(), policy_spec]
    if tenants:
        in_specs.append(_HIER_SPEC)
    mapped = shard_map(
        partial(body, step_kw=step_kw),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(state_spec, (P(AXIS), P(AXIS), P(AXIS))),
        check_vma=False,
    )
    step = jax.jit(mapped, donate_argnums=(0,))
    _, reset = bucket_kernels.build_steps(cfg)
    _MESH_BUCKET_CACHE[key] = (step, reset)
    return step, reset


def replicate_state(state, mesh: Mesh):
    """Place a (host or single-device) state dict fully replicated on the mesh."""
    sh = NamedSharding(mesh, P())
    return {k: jax.device_put(v, sh) for k, v in state.items()}


def shard_batch(arr, mesh: Mesh):
    """Place a host batch array sharded over the mesh axis."""
    return jax.device_put(arr, NamedSharding(mesh, P(AXIS)))
