#!/usr/bin/env python3
"""Lower every device program a benchmark cell launches and write the
StableHLO texts, so two checkouts can be compared program by program.

    python3 tools/lowered_steps.py --repo /path/to/checkout --out DIR
    python3 tools/lowered_steps.py --compare DIR_A DIR_B

The first form imports ``ratelimiter_tpu`` from ``--repo`` (default: this
file's checkout), builds a limiter for each of chipbench/configs/*.json
at its rehearsal width and lowers, without running anything: the serving
step on both lanes (finalized hashes, raw ids to premix), the reset and
rotate controls, the replicated mesh's step in both merge modes and, for
a ``--router collective`` config, the routed step over its one staged
operand (four operands in a checkout before PR 45); for a ``--backend
dense`` config (they live under configs/added/) the dense limiter's
serving step on both lanes and its reclaim / forget / clear_rem controls
(ISSUE 43), at ONE chip's capacity where the file spans several
(``capacity_a_chip``, ISSUE 51: a slice's programs are a single table's).
One ``<name>.mlir`` a program plus ``index.json`` (name -> jit module
name, sha256). It reads only the limiter's placement hooks
and ``_step`` / ``_reset_step`` / ``_rollover``, which every checkout
since PR 26 has, and the dense limiter's ``_reclaim_step`` /
``_forget_step`` / ``_clear_rem_step`` / ``_fresh`` (since PR 33).

``--batch N`` lowers the steps of an N-row dispatch instead of the
rehearsal's 256, ``--published`` at the configurations' published widths,
and ``--as-tpu`` with the strategy predicates of ops/sortmerge.py
answering as they do on the chip (``on_tpu`` patched in that module
only: the text is still this backend's StableHLO, which is what differs
between two checkouts when a predicate picks another body) — together,
the programs a large dispatch launches on the chip (ISSUE 36).

The second form exits 0 when the two directories hold the same programs
with the same texts and module names, 1 (naming each difference) when
not. A refactor of the step's builders that claims "no compiled program
changes" is held to it (ISSUE 30). CPU only: it needs four virtual
devices for the mesh programs, so it sets the platform itself.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
from pathlib import Path

B = 256  # the rehearsal's --max-batch: one padded frame
PUBLISHED = False


def _configs(repo: Path):
    from ratelimiter_tpu import Algorithm, Config, MeshSpec, SketchParams

    for path in sorted((repo / "chipbench" / "configs").glob("*.json")):
        c = json.loads(path.read_text())
        if not PUBLISHED:
            c.update(c.get("rehearsal", {}))
        flags = c["server_flags"]
        router = (flags[flags.index("--router") + 1]
                  if "--router" in flags else "host")
        backend = flags[flags.index("--backend") + 1]
        yield path.stem, backend, Config(
            algorithm=Algorithm(c["algorithm"]), limit=c["limit"],
            window=float(c["window_s"]),
            sketch=SketchParams(depth=c["depth"], width=c["width"],
                                sub_windows=c["sub_windows"]),
            mesh=MeshSpec(devices=c["chips"] if backend == "mesh" else None,
                          router=router))


def _dense_configs(repo: Path):
    from ratelimiter_tpu import Algorithm, Config, DenseParams

    for path in sorted((repo / "chipbench" / "configs").glob("**/*.json")):
        c = json.loads(path.read_text())
        if not PUBLISHED:
            c.update(c.get("rehearsal", {}))
        flags = c["server_flags"]
        if flags[flags.index("--backend") + 1] != "dense":
            continue
        yield path.stem, Config(
            algorithm=Algorithm(c["algorithm"]), limit=c["limit"],
            window=float(c["window_s"]),
            # A file of several chips states the host's total beside
            # what ONE chip's table holds: the programs are a chip's.
            dense=DenseParams(capacity=c.get("capacity_a_chip",
                                             c["capacity"]),
                              lanes=c["lanes"],
                              probe_bound=c["probe_bound"]))


def _programs(repo: Path):
    """(name, jitted callable, args) for every program of every config."""
    import jax
    import numpy as np

    from ratelimiter_tpu import Algorithm
    from ratelimiter_tpu.algorithms.sketch import (
        SketchLimiter, SketchTokenBucketLimiter)
    from ratelimiter_tpu.core.clock import ManualClock
    from ratelimiter_tpu.ops import route_kernels
    from ratelimiter_tpu.parallel import mesh_kernels
    from ratelimiter_tpu.parallel.collective import CollectiveMeshLimiter
    from ratelimiter_tpu.parallel.limiter import (
        MeshSketchLimiter, MeshTokenBucketLimiter)
    from ratelimiter_tpu.parallel.mesh import make_mesh

    clock = ManualClock(1_700_000_000.0)

    def serving(name, lim):
        padded = lim._padded_size(B)
        slot = np.zeros(2 * padded + 1, np.uint64)
        with lim._lock:
            args = (lim._state, *lim._stage_operands(slot, padded),
                    lim._policy_device())
            yield f"{name}.hashed", lim._step, args
            yield f"{name}.premix", lim._get_ids_step(), args

    for name, cfg in _dense_configs(repo):
        from ratelimiter_tpu.algorithms.dense import DenseLimiter

        dense = DenseLimiter(cfg, clock)
        yield from serving(name, dense)
        key = (np.zeros(1, np.uint64), np.ones(1, bool), dense._fresh)
        yield (f"{name}.reclaim", dense._reclaim_step,
               (dense._state, np.int64(1_700_000_000_000_000), dense._fresh))
        yield f"{name}.forget", dense._forget_step, (dense._state, *key)
        yield f"{name}.clear_rem", dense._clear_rem_step, (dense._state, *key)

    for name, backend, cfg in _configs(repo):
        bucket = cfg.algorithm is Algorithm.TOKEN_BUCKET
        one = (SketchTokenBucketLimiter if bucket else SketchLimiter)(
            cfg, clock)
        yield from serving(name, one)
        h = jax.device_put(np.ones(1, np.uint32))
        now = np.int64(1_700_000_000_000_000)
        yield f"{name}.reset", one._reset_step, (one._state, h, h, now)
        if not bucket:
            yield f"{name}.rotate", one._rollover, (one._state, np.int64(7))
        for merge in mesh_kernels.MERGE_MODES:
            mesh_lim = (MeshTokenBucketLimiter if bucket
                        else MeshSketchLimiter)(
                cfg, clock, mesh=make_mesh(n_devices=4), merge=merge)
            yield from serving(f"{name}.mesh-{merge}", mesh_lim)
        if backend == "mesh" and cfg.mesh.router == "collective":
            coll = CollectiveMeshLimiter(cfg, clock)
            n = coll.n_slices
            L = B // n
            C = route_kernels.bin_capacity(L, n, cfg.mesh.bin_headroom)
            if hasattr(coll, "_acquire_slot"):
                # Since PR 45 the frame is ONE staged operand, a row a
                # device, placed as the launch places it.
                slot = coll._acquire_slot(L)
                slot[:] = 0
                frame = (jax.device_put(slot, coll._frame_sharding),)
            else:
                # A checkout before PR 45: two sharded columns and two
                # host scalars.
                frame = (mesh_kernels.shard_batch(np.zeros(B, np.uint64),
                                                  coll.mesh),
                         mesh_kernels.shard_batch(np.zeros(B, np.int32),
                                                  coll.mesh),
                         np.int64(B), now)
            for s in coll.slices:
                s._lock.acquire()
            try:
                mut, ro = coll._assemble_state()
                args = (mut, ro, *frame, coll._policy_mesh())
            finally:
                for s in coll.slices:
                    s._lock.release()
            for premix in (False, True):
                step = route_kernels.build_routed_step(
                    cfg, coll.mesh, premix=premix, L=L, capacity=C)
                yield (f"{name}.routed-{'premix' if premix else 'hashed'}",
                       step, args)


def write(repo: Path, out: Path, as_tpu: bool = False) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, str(repo))
    from ratelimiter_tpu.core import jaxcfg

    jaxcfg.configure()
    if as_tpu:
        from ratelimiter_tpu.ops import sortmerge

        sortmerge.on_tpu = lambda: True
    out.mkdir(parents=True, exist_ok=True)
    index = {}
    for name, fn, args in _programs(repo):
        text = fn.lower(*args).as_text()
        (out / f"{name}.mlir").write_text(text)
        module = re.search(r"module @(\w+)", text)
        index[name] = {"module": module.group(1) if module else None,
                       "sha256": hashlib.sha256(text.encode()).hexdigest()}
    (out / "index.json").write_text(json.dumps(index, indent=1,
                                               sort_keys=True))
    print(f"{len(index)} programs lowered from {repo} into {out}")
    return 0


def compare(a: Path, b: Path) -> int:
    ia = json.loads((a / "index.json").read_text())
    ib = json.loads((b / "index.json").read_text())
    diffs = [f"{n}: only in {a if n in ia else b}"
             for n in sorted(set(ia) ^ set(ib))]
    same = 0
    for n in sorted(set(ia) & set(ib)):
        if ia[n]["module"] != ib[n]["module"]:
            diffs.append(f"{n}: module {ia[n]['module']} != "
                         f"{ib[n]['module']}")
        elif ia[n]["sha256"] != ib[n]["sha256"]:
            diffs.append(f"{n}: text differs (diff {a / n}.mlir "
                         f"{b / n}.mlir)")
        else:
            same += 1
    for d in diffs:
        print(d)
    print(f"{same} of {len(set(ia) | set(ib))} programs identical")
    return 1 if diffs else 0


def main() -> int:
    global B, PUBLISHED
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", type=Path,
                    default=Path(__file__).resolve().parent.parent)
    ap.add_argument("--out", type=Path)
    ap.add_argument("--compare", nargs=2, type=Path, metavar="DIR")
    ap.add_argument("--batch", type=int, default=B,
                    help="rows of the dispatch whose steps are lowered")
    ap.add_argument("--published", action="store_true",
                    help="the configurations' published widths")
    ap.add_argument("--as-tpu", action="store_true",
                    help="ops/sortmerge.py's predicates answer as on a TPU")
    args = ap.parse_args()
    if args.compare:
        return compare(*args.compare)
    if args.out is None:
        ap.error("--out or --compare")
    B, PUBLISHED = args.batch, args.published
    return write(args.repo.resolve(), args.out, args.as_tpu)


if __name__ == "__main__":
    sys.exit(main())
