"""The device step's contract (ISSUE 30): which compiled program decides
a batch under a config is derived in ONE function per rule
(``sketch_kernels.step_statics`` / ``bucket_kernels.step_statics``), and
every builder keys its memo on that mapping's items plus what it binds
itself. Held here, for every builder that survives:

* (a) a config built twice, and configs that differ only in what no step
  reads (persistence, fail_open, mesh placement — and, for the bucket,
  the window rule's sub-window / conservative-update / side-table
  fields) give the SAME compiled callable: an ``update_*`` of those
  recompiles nothing;
* (b) a change to each field the statics read gives a DIFFERENT one: a
  key that forgot a field would serve the old limit's program after
  ``update_limit``;
* (c) ``update_limit`` / ``update_window`` on the replicated-mesh
  limiters leave the mesh's step installed (its output sharded over the
  mesh axis) through the base class's one hook, which the four deleted
  ``_apply_*`` overrides existed to ensure;
* the server's banner still carries the ``kernels=`` word both of its
  parsers require (chipbench/runner.py, chip_smoke.py), as a constant.
"""

import ast
import re
import types
from dataclasses import replace
from functools import partial
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from ratelimiter_tpu import Algorithm, Config, ManualClock, SketchParams
from ratelimiter_tpu.core.config import (
    HierarchySpec,
    MeshSpec,
    PersistenceSpec,
)
from ratelimiter_tpu.ops import bucket_kernels, route_kernels, sketch_kernels
from ratelimiter_tpu.parallel import (
    MeshSketchLimiter,
    MeshTokenBucketLimiter,
    make_mesh,
    mesh_kernels,
)

REPO = Path(__file__).resolve().parent.parent
T0 = 1_700_000_000.25
RULES = {"window": Algorithm.SLIDING_WINDOW, "bucket": Algorithm.TOKEN_BUCKET}
KERNELS = {"window": sketch_kernels, "bucket": bucket_kernels}


def _cfg(rule: str, **kw) -> Config:
    """A geometry no other test file builds, so nothing here is served
    from (or leaves behind) another test's memo entry."""
    sketch = dict(depth=3, width=128, sub_windows=6)
    sketch.update(kw.pop("sketch", {}))
    base = dict(algorithm=RULES[rule], limit=11, window=42.0,
                sketch=SketchParams(**sketch))
    base.update(kw)
    return Config(**base)


def _mesh():
    return make_mesh(n_devices=4)


def _serving(premix, rule, cfg):
    return KERNELS[rule].build_hashed_step(cfg, premix=premix)


def _meshed(merge, rule, cfg):
    return mesh_kernels.build_mesh_hashed_step(cfg, _mesh(), merge)


def _routed(rule, cfg):
    return route_kernels.build_routed_step(cfg, _mesh(), premix=False, L=8,
                                           capacity=8)


#: name -> build(rule, cfg); every one binds the hash seed.
BUILDERS = {
    "step-hashed": partial(_serving, False),
    "step-premix": partial(_serving, True),
    "mesh-gather": partial(_meshed, "gather"),
    "mesh-delta": partial(_meshed, "delta"),
    "routed": _routed,
}

#: field -> the one-field change. Every one is read by the window rule's
#: statics; the bucket's read all but WINDOW_ONLY. What a builder that
#: does not read a field must do with it is in the (a) test.
CHANGES = {
    "limit": dict(limit=12),
    "window": dict(window=84.0),
    "depth": dict(sketch=dict(depth=2)),
    "width": dict(sketch=dict(width=256)),
    "admission_iters": dict(max_batch_admission_iters=1),
    "tenants": dict(hierarchy=HierarchySpec(tenants=4)),
    "seed": dict(sketch=dict(seed=7)),
    "sub_windows": dict(sketch=dict(sub_windows=3)),
    "conservative_update": dict(sketch=dict(conservative_update=False)),
    "hh_slots": dict(sketch=dict(hh_slots=16)),
}
WINDOW_ONLY = ("sub_windows", "conservative_update", "hh_slots")


def _reads(rule: str, field: str) -> bool:
    return rule == "window" or field not in WINDOW_ONLY


needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 (virtual) devices")
CASES = [pytest.param(rule, name,
                      marks=[needs_mesh] if name[:4] in ("mesh", "rout")
                      else [])
         for rule in RULES for name in BUILDERS]


@pytest.mark.parametrize("rule, builder", CASES)
def test_what_no_step_reads_recompiles_nothing(rule, builder):
    build = partial(BUILDERS[builder], rule)
    first = build(_cfg(rule))
    assert build(_cfg(rule)) is first
    for other in (
            dict(fail_open=False),
            dict(persistence=PersistenceSpec(dir="/nonexistent",
                                             snapshot_interval=7.0)),
            dict(mesh=MeshSpec(devices=2, bin_headroom=3.0)),
            dict(key_prefix="another")):
        assert build(_cfg(rule, **other)) is first, other
    for field, change in CHANGES.items():
        if not _reads(rule, field):
            assert build(_cfg(rule, **change)) is first, field


@pytest.mark.parametrize("rule, builder, field", [
    pytest.param(*case.values, field, marks=case.marks)
    for case in CASES for field in CHANGES
    if _reads(case.values[0], field)])
def test_a_field_the_step_reads_gives_another_program(rule, builder, field):
    build = partial(BUILDERS[builder], rule)
    first = build(_cfg(rule))
    changed = build(_cfg(rule, **CHANGES[field]))
    assert changed is not first
    # ... and the change is itself remembered, not rebuilt every call.
    assert build(_cfg(rule, **CHANGES[field])) is changed
    assert build(_cfg(rule)) is first


@pytest.mark.parametrize("rule", list(RULES))
def test_the_statics_are_the_bodys_keywords_and_the_memo_key(rule):
    """step_statics names exactly keyword arguments of the body it is
    for (a typo would only fail at trace time), and two configs with
    equal statics share every program."""
    import inspect

    body = (sketch_kernels._sketch_step if rule == "window"
            else bucket_kernels._bucket_step)
    kw = KERNELS[rule].step_statics(_cfg(rule))
    params = inspect.signature(body).parameters
    assert set(kw) <= {n for n, p in params.items()
                       if p.kind is p.KEYWORD_ONLY}
    step, kw2, _pack = route_kernels.step_rule(_cfg(rule))
    assert step is body and kw2 == kw
    assert route_kernels.state_layout(_cfg(rule))[0] == (
        "sketch" if rule == "window" else "bucket")


@pytest.mark.parametrize("rule", list(RULES))
def test_controls_are_keyed_on_what_they_read(rule):
    """reset (and the window rule's rotate) read the geometry (the
    bucket's reset the refill rate too), not the admission loop: another
    iteration count reuses them, another width does not."""
    build = KERNELS[rule].build_controls
    first = build(_cfg(rule))
    assert len(first) == (2 if rule == "window" else 1)
    assert all(a is b for a, b in zip(
        build(_cfg(rule, max_batch_admission_iters=1)), first))
    assert all(a is not b for a, b in zip(
        build(_cfg(rule, sketch=dict(width=256))), first))


# --------------------------------------- (c) the mesh keeps its own step

@needs_mesh
@pytest.mark.parametrize("update", ["update_limit", "update_window"])
@pytest.mark.parametrize("merge", ["gather", "delta"])
@pytest.mark.parametrize("rule", list(RULES))
def test_a_dynamic_update_leaves_the_mesh_step_installed(rule, merge,
                                                         update):
    cls = MeshSketchLimiter if rule == "window" else MeshTokenBucketLimiter
    mesh = _mesh()
    lim = cls(_cfg(rule), ManualClock(T0), mesh=mesh, merge=merge)
    ids = np.arange(1, 41, dtype=np.uint64)
    lim.resolve(lim.launch_hashed(ids))
    getattr(lim, update)(5 if update == "update_limit" else 21.0)

    assert lim._step is mesh_kernels.build_mesh_hashed_step(
        lim.config, mesh, merge)
    assert lim._get_ids_step() is mesh_kernels.build_mesh_hashed_step(
        lim.config, mesh, merge, premix=True)
    # The controls are the rule's single-chip ones, on every placement.
    assert lim._reset_step is KERNELS[rule].build_controls(lim.config)[0]
    for launch in (lim.launch_hashed, lim.launch_ids):
        ticket = launch(ids)
        assert ticket.outs.sharding.spec == P(mesh_kernels.AXIS)
        assert ({s.device for s in ticket.outs.addressable_shards}
                == set(mesh.devices.flat))
        res = lim.resolve(ticket)
        assert res.allowed.shape == (40,)
    for leaf in lim._state.values():
        assert leaf.sharding.is_fully_replicated
        assert set(leaf.devices()) == set(mesh.devices.flat)
    if update == "update_limit":
        # The new limit decides: six of one key, five admitted.
        res = lim.allow_hashed(np.full(6, 999, dtype=np.uint64))
        assert int(res.allowed.sum()) == 5
        if rule == "window":
            assert lim.mass_budget == lim.config.sketch.mass_budget(5)
    lim.reset("k")
    lim.close()


# ------------------------------------------------- the banner's word

def _pattern_in(path: Path, name: str = "_BANNER"):
    """The compiled regex a module assigns to ``name``, evaluated from
    the file's own source (chipbench/ is not importable from tier-1 and
    must not be edited: the server is held to the text that is there)."""
    for node in ast.parse(path.read_text()).body:
        if (isinstance(node, ast.Assign)
                and getattr(node.targets[0], "id", None) == name):
            return eval(compile(ast.Expression(node.value), str(path),
                                "eval"), {"re": re})
    raise AssertionError(f"{path} assigns no {name}")


@pytest.mark.parametrize("reader", ["chipbench/runner.py", "chip_smoke.py"])
def test_the_banner_matches_the_pattern_of_each_of_its_readers(reader):
    from ratelimiter_tpu.algorithms.sketch import SketchLimiter
    from ratelimiter_tpu.serving.__main__ import _device_report

    lim = SketchLimiter(_cfg("window"), ManualClock(T0))
    report = _device_report(types.SimpleNamespace(backend="sketch"), [lim])
    m = _pattern_in(REPO / reader).search(f"serving(native) x {report} ")
    assert m, report
    assert m["kernels"] == "jnp" and m["platform"] == "cpu"
    assert m["slices"] == str(jax.devices()[0].id)
    lim.close()


def test_the_option_and_the_field_are_gone():
    from ratelimiter_tpu.serving.__main__ import build_parser

    assert "kernels" not in SketchParams.__dataclass_fields__
    with pytest.raises(TypeError):
        SketchParams(kernels="jnp")
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--kernels", "jnp"])
    # A change of what no step reads is what dataclasses.replace gives
    # update paths: the same object graph, one field apart.
    cfg = _cfg("window")
    assert (sketch_kernels.step_statics(replace(cfg, fail_open=False))
            == sketch_kernels.step_statics(cfg))


# ------------------------------------- the dense step keeps the contract
#
# The dense backend's served step (ops/dense_kernels.build_hashed_step,
# module ``jit_dense_step``) joined the hashed lane in ISSUE 33: one
# derivation of its statics (the rule's parameters and the directory's
# geometry), every builder keyed on it.

from ratelimiter_tpu import DenseParams  # noqa: E402
from ratelimiter_tpu.ops import dense_kernels, directory  # noqa: E402

DENSE_RULES = {"fixed": Algorithm.FIXED_WINDOW,
               "sliding": Algorithm.SLIDING_WINDOW,
               "bucket": Algorithm.TOKEN_BUCKET}
DENSE_CAPACITY = 96


def _dense_cfg(rule: str, **kw) -> Config:
    dense = dict(capacity=DENSE_CAPACITY, lanes=8, probe_bound=5)
    dense.update(kw.pop("dense", {}))
    base = dict(algorithm=DENSE_RULES[rule], limit=11, window=42.0,
                dense=DenseParams(**dense))
    base.update(kw)
    return Config(**base)


def _dense_step(premix, cfg, capacity=DENSE_CAPACITY):
    return dense_kernels.build_hashed_step(cfg, capacity, premix=premix)


DENSE_CHANGES = {
    "limit": dict(limit=12),
    "window": dict(window=84.0),
    "admission_iters": dict(max_batch_admission_iters=1),
    "lanes": dict(dense=dict(lanes=4)),
    "probe_bound": dict(dense=dict(probe_bound=3)),
}


@pytest.mark.parametrize("premix", [False, True], ids=["hashed", "premix"])
@pytest.mark.parametrize("rule", list(DENSE_RULES))
def test_dense_what_no_step_reads_recompiles_nothing(rule, premix):
    first = _dense_step(premix, _dense_cfg(rule))
    assert _dense_step(premix, _dense_cfg(rule)) is first
    for other in (
            dict(fail_open=False),
            dict(persistence=PersistenceSpec(dir="/nonexistent",
                                             snapshot_interval=7.0)),
            dict(sketch=SketchParams(depth=2, width=256)),
            dict(key_prefix="another"),
            # The table's size is the limiter's, not the config's field.
            dict(dense=dict(capacity=4096)),
            # A bound past the number of buckets is the number of buckets.
            dict(dense=dict(lanes=48, probe_bound=2)),):
        got = _dense_step(premix, _dense_cfg(rule, **other))
        assert (got is first) == ("lanes" not in other.get("dense", {})), \
            other
    assert _dense_step(not premix, _dense_cfg(rule)) is not first


@pytest.mark.parametrize("field", list(DENSE_CHANGES) + ["capacity"])
@pytest.mark.parametrize("rule", list(DENSE_RULES))
def test_dense_a_field_the_step_reads_gives_another_program(rule, field):
    first = _dense_step(False, _dense_cfg(rule))
    if field == "capacity":
        build = partial(_dense_step, False, _dense_cfg(rule), 192)
    else:
        build = partial(_dense_step, False,
                        _dense_cfg(rule, **DENSE_CHANGES[field]))
    changed = build()
    assert changed is not first
    assert build() is changed
    assert _dense_step(False, _dense_cfg(rule)) is first


@pytest.mark.parametrize("rule", list(DENSE_RULES))
def test_dense_statics_are_the_bodys_keywords(rule):
    import inspect

    cfg = _dense_cfg(rule)
    kw = dense_kernels.step_statics(cfg, DENSE_CAPACITY)
    keywords = lambda fn: {n for n, p in  # noqa: E731
                           inspect.signature(fn).parameters.items()
                           if p.kind is p.KEYWORD_ONLY}
    body = dense_kernels._rule(cfg.algorithm)
    assert set(kw) <= keywords(dense_kernels._dense_step_staged) | \
        keywords(body)
    assert keywords(body) <= set(kw)
    geo = directory.geometry(DENSE_CAPACITY, 8, 5)
    assert geo == dict(nb=12, w=8, pb=5)
    assert {k: kw[k] for k in geo} == geo
    assert (dense_kernels.step_statics(replace(cfg, fail_open=False),
                                       DENSE_CAPACITY) == kw)


@pytest.mark.parametrize("rule", list(DENSE_RULES))
def test_dense_controls_are_keyed_on_what_they_read(rule):
    """reclaim reads the geometry, the rule's stamp column and the
    window (its horizon); forget and clear_rem the geometry alone."""
    build = partial(dense_kernels.build_controls, capacity=DENSE_CAPACITY)
    first = build(_dense_cfg(rule))
    assert len(first) == 3
    same = build(_dense_cfg(rule, limit=12, max_batch_admission_iters=1))
    assert all(a is b for a, b in zip(same, first))
    window = build(_dense_cfg(rule, window=84.0))
    assert window[0] is not first[0]
    assert window[1] is first[1] and window[2] is first[2]
    assert all(a is not b for a, b in zip(
        build(_dense_cfg(rule, dense=dict(lanes=4))), first))


def test_the_dense_banner_names_its_capacity_and_still_matches():
    from ratelimiter_tpu.algorithms.dense import DenseLimiter
    from ratelimiter_tpu.serving.__main__ import (
        _device_report,
        build_parser,
    )

    args = build_parser().parse_args(
        ["--backend", "dense", "--dense-capacity", "4096"])
    assert args.dense_capacity == 4096
    assert build_parser().parse_args([]).dense_capacity \
        == DenseParams.capacity == 65536
    lim = DenseLimiter(_dense_cfg("bucket"), ManualClock(T0))
    report = _device_report(args, [lim])
    assert report.endswith(" dense_capacity=4096")
    m = _pattern_in(REPO / "chipbench/runner.py").search(
        f"serving(native) x {report} http:1")
    assert m and m["slices"] == str(jax.devices()[0].id)
    lim.close()
