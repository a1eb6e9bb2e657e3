"""Hierarchical cascades + adaptive control (ADR-020).

Three things in one runnable, in-process tour:

1. **the cascade** — with ``HierarchySpec`` every decision evaluates
   key → tenant → global scopes in ONE device dispatch (tenant ids
   derive on device from the key→tenant map; nothing tenant-shaped is
   ever on the wire), with all-or-nothing admission;
2. **weighted fair sharing** — under global contention, tenants split
   the contended mass proportionally to their weights, on device;
3. **the AIMD controller** — a hot-tenant storm saturates the global
   scope, the controller tightens the attacker's EFFECTIVE limit
   (floor-bounded, ceiling untouched), and after the storm clears it
   additively recovers back to the ceiling.

    JAX_PLATFORMS=cpu python examples/17_multitenant.py

Serving form: ``--tenants/--tenant/--assign/--controller`` on the
server binary, live management over bearer-gated ``/v1/tenants``.
Runbook: docs/OPERATIONS.md §11; decisions: docs/ADR/020.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")  # device backends need x64

import numpy as np  # noqa: E402

from ratelimiter_tpu import (  # noqa: E402
    Algorithm,
    Config,
    HierarchySpec,
    ManualClock,
    create_limiter,
)
from ratelimiter_tpu.core.config import SketchParams  # noqa: E402
from ratelimiter_tpu.hierarchy import AIMDController, AIMDGains  # noqa: E402

T0 = 1_700_000_000.0
WINDOW = 60.0


def cascade_basics():
    print("== 1. the cascade: key -> tenant -> global, one dispatch ==")
    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=4, window=WINDOW,
        sketch=SketchParams(depth=2, width=1 << 12, sub_windows=4),
        hierarchy=HierarchySpec(tenants=4, global_limit=50))
    lim = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
    lim.set_tenant("gold", 6, weight=3)
    for k in ("g1", "g2", "g3"):
        lim.assign_tenant(k, "gold")

    # Per-key limit is 4, but gold's TENANT scope caps its three keys
    # at 6 per window combined: 12 attempts admit only 6.
    got = sum(int(lim.allow(k).allowed)
              for k in ("g1", "g2", "g3") * 4)
    print(f"  gold demand 12 (3 keys x 4 under per-key limit 4) "
          f"-> admitted {got} (tenant ceiling 6)")
    # Unassigned keys ride the default tenant -- gold's cap never
    # touches them.
    print(f"  unassigned key: allowed={lim.allow('other').allowed} "
          f"(default tenant, not gold)")
    st = lim.hierarchy_stats()
    print(f"  in-window mass: gold={st['tenants']['gold']['in_window']} "
          f"global={st['global']['in_window']}")
    lim.close()


def fair_sharing():
    print("== 2. weighted fair sharing under global contention ==")
    weights = {"small": 1, "mid": 2, "big": 5}
    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=1000, window=WINDOW,
        sketch=SketchParams(depth=2, width=1 << 12, sub_windows=4),
        hierarchy=HierarchySpec(tenants=4, global_limit=96))
    lim = create_limiter(cfg, backend="sketch", clock=ManualClock(T0))
    rng = np.random.default_rng(5)
    keys = []
    for name, w in weights.items():
        lim.set_tenant(name, 10_000, weight=w)
        for i in range(16):
            lim.assign_tenant(f"{name}_k{i}", name)
            keys.extend([f"{name}_k{i}"] * 4)
    rng.shuffle(keys)

    # Every key bursts at once (a thundering herd): demand 192 against
    # global 96. The contended mass splits ~ 1:2:5, on device.
    out = lim.allow_batch(keys)
    got = np.asarray(out.allowed, dtype=bool)
    per = {name: int(sum(ok for k, ok in zip(keys, got)
                         if k.startswith(name))) for name in weights}
    print(f"  demand {len(keys)} vs global 96 -> admitted {int(got.sum())}")
    for name, w in weights.items():
        print(f"    {name:6s} weight {w}: admitted {per[name]}")
    lim.close()


def adaptive_control():
    print("== 3. AIMD: tighten under a hot-tenant storm, recover after ==")
    cfg = Config(
        algorithm=Algorithm.SLIDING_WINDOW, limit=100_000, window=WINDOW,
        sketch=SketchParams(depth=2, width=1 << 12, sub_windows=4),
        hierarchy=HierarchySpec(tenants=4, global_limit=1200))
    clock = ManualClock(T0)
    lim = create_limiter(cfg, backend="sketch", clock=clock)
    lim.set_tenant("attacker", 1000, weight=1, floor=50)
    lim.set_tenant("victim", 1000, weight=6, floor=50)
    atk = [f"atk{i}" for i in range(40)]
    vic = [f"vic{i}" for i in range(8)]
    for k in atk:
        lim.assign_tenant(k, "attacker")
    for k in vic:
        lim.assign_tenant(k, "victim")
    # In-process tick driving (a server runs this on a background
    # thread via --controller).
    ctl = AIMDController(
        lim, interval=999.0,
        gains=AIMDGains(decrease_factor=0.7, increase_fraction=0.2,
                        cooldown_s=0.0))

    rng = np.random.default_rng(7)

    def frames(n, size, atk_frac):
        for _ in range(n):
            n_atk = int(size * atk_frac)
            keys = ([atk[int(i)] for i in
                     rng.integers(0, len(atk), size=n_atk)]
                    + [vic[int(i)] for i in
                       rng.integers(0, len(vic), size=size - n_atk)])
            rng.shuffle(keys)
            yield keys

    tick = 0.0
    for phase, n, size, frac in (("baseline", 6, 160, 0.3),
                                 ("storm", 6, 640, 0.9),
                                 ("recovery", 6, 160, 0.3)):
        clock.advance(2.5 * WINDOW)       # window rolls between phases
        lim.allow("phase-warmup")
        timeline = []
        for keys in frames(n, size, frac):
            lim.allow_batch(keys)
            ctl.tick(tick)                # off the decision path
            tick += 1.0
            timeline.append(lim.effective_limits()["attacker"])
        print(f"  {phase:9s} attacker effective limit per frame: "
              f"{timeline}")
    print(f"  controller moves: tightened={ctl.tightened} "
          f"relaxed={ctl.relaxed} (ceiling 1000, floor 50)")
    lim.close()


if __name__ == "__main__":
    cascade_basics()
    fair_sharing()
    adaptive_control()
    print("OK")
