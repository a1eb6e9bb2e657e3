"""The host cycle of an UNTRACED server, read off the door's always-on
thread-state counters (PR 37; PERF.md section 5's host-cycle table): start the cell's server with its configuration's flags alone,
drive the cell's own traffic, scrape /metrics over the wire 3 s and 23 s
into the load, and print one JSON line: per dispatch, each thread state,
the CPU of each door thread, the door's exact stage sums, and the
generator's completed decisions a second.

    chiprun -- python3 tools/door_states.py <cell> <seed> [extra server flags]

The parent never imports JAX (the server child holds the chip); with
JAX_PLATFORMS=cpu it is a rehearsal at the cell's tiny geometry (counts
only).
"""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import promtext, runner            # noqa: E402
from chipbench.wire import Wire                   # noqa: E402

F = "rate_limiter_door_thread_seconds_total"
C = "rate_limiter_door_thread_cpu_seconds_total"
S = "rate_limiter_door_stage_seconds_total"
N = "rate_limiter_door_dispatches_total"
U = "rate_limiter_result_native_unpacks_total"


def main() -> int:
    cell = runner.load_cell(sys.argv[1])
    seed = int(sys.argv[2])
    extra = sys.argv[3:]
    cell["config"] = dict(cell["config"], server_flags=list(
        cell["config"]["server_flags"]) + extra)
    out_dir = os.path.join(runner.HERE, "out", f"states-{sys.argv[1]}-{seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    binary, _ = runner.build_loadgen()
    seconds = 20.0
    # Spawned before the server: its tables (80 M keys: 7-20 s) are built
    # beside the server's start, and the window is the one its own
    # ``schedule`` line states (chipbench/runner.Generator, PR 50).
    gen = runner.Generator(binary, cell, seed, seconds)
    try:
        with runner.serving(cell, out_dir, trace=False) as srv:
            t0 = gen.start(srv.port)["t_window_start"]
            time.sleep(max(0.0, t0 - time.monotonic()))
            with Wire(srv.port) as wire:
                a_t, a = time.monotonic(), promtext.parse(wire.metrics())
            time.sleep(max(0.0, t0 + seconds - 0.05 - time.monotonic()))
            with Wire(srv.port) as wire:
                b_t, b = time.monotonic(), promtext.parse(wire.metrics())
            g = gen.result()
    finally:
        gen.stop()
    n = promtext.delta(a, b, N)
    row = {"cell": sys.argv[1], "seed": seed, "extra": extra,
           "device": srv.device, "scrape_s": b_t - a_t, "dispatches": n,
           "decisions_per_s": g["completed"] / g["window_s"]}
    row["rows_per_dispatch"] = row["decisions_per_s"] * row["scrape_s"] / n
    # Which pass rebuilt the reply columns (PR 52): 1.0 native, 0.0 the
    # NumPy twin; None on a program without the counter.
    row["native_unpacks_per_dispatch"] = (
        promtext.delta(a, b, U) / n if any(k == U for k, _ in b) else None)
    for thread, states in (("dispatcher", ("idle", "gather", "gil", "python",
                                           "slot", "other")),
                           ("completer", ("idle", "gil", "python", "other"))):
        wall = promtext.delta(a, b, F, thread=thread)
        row[thread] = {st: promtext.delta(a, b, F, thread=thread, state=st)
                       / n * 1e6 for st in states}
        row[thread]["wall_s"] = wall
        row[thread]["cpu_us"] = promtext.delta(a, b, C, thread=thread) / n * 1e6
        row[thread]["cpu_pct"] = 100 * promtext.delta(a, b, C, thread=thread) \
            / wall if wall else None
    for thread in ("io", "responder"):
        row[thread + "_cpu_us"] = promtext.delta(a, b, C, thread=thread) / n * 1e6
    row["stage_us"] = {st: promtext.delta(a, b, S, stage=st) / n * 1e6
                       for st in ("io", "dispatch", "device", "complete")}
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
