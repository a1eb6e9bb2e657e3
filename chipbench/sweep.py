"""Sweep one field of a cell's traffic mix against ONE running server:

    python -m chipbench.sweep --workload wide-string-rpc --field rate \\
        --values 100000,200000,400000 --seconds 8

For each value: the cell's generator with that field overridden, on the
run's own schedule (`runner.Generator`: tables built, then 3 s of
warm-up, ``--seconds`` of window). Prints one JSON line per value —
completed decisions per second, latency, the generator's lateness, and
the frames pending at the end of each second (a backlog that grows says
the rate is above the knee). It is how the knee of an open-loop mix and
the in-flight check of a closed-loop mix were found (PERF.md); a run of
the benchmark never calls it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

from chipbench import runner


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--field", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = runner.load_cell(args.workload)
    out_dir = os.path.join(runner.HERE, "out", f"sweep-{args.workload}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    binary, _ = runner.build_loadgen()
    kind = type(runner.TRAFFIC_DEFAULTS[args.field] or 0.0)
    with runner.serving(cell, out_dir, trace=False) as srv:
        print(json.dumps({"banner": srv.banner}), flush=True)
        for i, raw in enumerate(args.values.split(",")):
            cell["traffic"][args.field] = kind(float(raw))
            generator = runner.Generator(binary, cell, args.seed + i,
                                         args.seconds)
            try:
                generator.start(srv.port)
                gen = generator.result()
            except runner.RunFailure as exc:
                print(json.dumps({args.field: raw, "failed": str(exc)[-500:]}),
                      flush=True)
                continue
            print(json.dumps({
                args.field: cell["traffic"][args.field],
                "offered_per_s": gen["sent"] / gen["window_s"],
                "completed_per_s": gen["completed"] / gen["window_s"],
                "latency_ms": gen["latency_ms"], "gen_late_ms": gen["gen_late_ms"],
                "unanswered": gen["unanswered"], "policy": gen["policy"],
                "error_frames": gen["error_frames"],
                "pending_frames_by_second": [s["pending_frames"]
                                             for s in gen["per_second"]],
                "completed_by_second": [s["completed"]
                                        for s in gen["per_second"]]}), flush=True)
    shutil.rmtree(os.path.join(out_dir, "tmp"), ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
