"""Run the benchmark suite: ``python -m benchmarks [--quick] [--only G]``.

Writes benchmarks/RESULTS.json (machine) and benchmarks/RESULTS.md
(human); both are scratch outputs (git-ignored).
"""

from __future__ import annotations

import argparse
import json
import os
import platform as _platform
import sys
import time
from datetime import datetime, timezone

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax

from ratelimiter_tpu.core import jaxcfg

# x64 + the persistent compile cache: the matrix touches many (shape,
# algo, backend) cells; caching makes re-runs cheap.
jaxcfg.configure()


def _render_multichip(ms: dict, route_phases: dict | None = None) -> list:
    """The multichip_scaling curve as a markdown table, with the
    collective-vs-host router comparison columns (ADR-024). n/a-safe by
    the e2e_mixed_* convention (ADR-013): a column whose key is absent —
    host-router-only runs, single-device JSONs, rows whose e2e leg
    errored — renders as ``n/a``, never as a silent 0."""
    def _rate(r: dict, k: str) -> str:
        v = r.get(k)
        return f"{v:,.0f}" if isinstance(v, (int, float)) else "n/a"

    lines = [
        "## Multichip scaling (mesh serving)", "",
        "Rows are decisions/s through the real native door. affine = "
        "shard-affine traffic (ADR-012), mixed = uniform per-frame "
        "fan-out (scatter-gather, ADR-013); collective columns are the "
        "same traffic served by `--router collective` (ADR-024, one "
        "shard_map all_to_all dispatch per frame). n/a = not measured "
        "in this run, never a silent zero.", "",
        "| n | device step/s | e2e affine/s | e2e mixed/s "
        "| collective affine/s | collective mixed/s | coll/host mixed |",
        "|---:|---:|---:|---:|---:|---:|---:|",
    ]
    for r in ms.get("rows", []):
        ratio = r.get("e2e_collective_vs_host_mixed")
        lines.append(
            f"| {r.get('n_devices', '?')} "
            f"| {_rate(r, 'device_step_decisions_per_sec')} "
            f"| {_rate(r, 'e2e_decisions_per_sec')} "
            f"| {_rate(r, 'e2e_mixed_decisions_per_sec')} "
            f"| {_rate(r, 'e2e_collective_decisions_per_sec')} "
            f"| {_rate(r, 'e2e_collective_mixed_decisions_per_sec')} "
            f"| {ratio if ratio is not None else 'n/a'} |")
    lines.append("")
    if route_phases:
        host = route_phases.get("host", {})
        coll = route_phases.get("collective", {})
        lines += [
            f"Route host phases (per {route_phases.get('frame_keys', '?')}"
            f"-key mixed frame, n={route_phases.get('n_devices', '?')}): "
            f"host router partition {host.get('partition_us', 'n/a')} µs "
            f"+ scatter {host.get('scatter_us', 'n/a')} µs vs collective "
            f"pad {coll.get('pad_us', 'n/a')} µs (partition/scatter "
            "eliminated on device, ADR-024).", ""]
    return lines


def _render_md(doc: dict) -> str:
    lines = [
        "# Benchmark results",
        "",
        f"- timestamp: {doc['meta']['timestamp']}",
        f"- platform: {doc['meta']['jax_platform']} "
        f"({doc['meta']['device_count']} device(s))",
        f"- mode: {'quick' if doc['meta']['quick'] else 'full'}",
        "",
    ]
    if "matrix" in doc:
        lines += ["## Matrix (reference 31-benchmark analog)", "",
                  "µs/call is wall clock and pays the full host↔device "
                  "round trip per dispatch; device µs/step is the "
                  "scan-amortized on-device "
                  "compute for the same batch shape (blank for scalar "
                  "shapes; n/a where the cell could not be measured — "
                  "host backends, or an RTT sample that swallowed the "
                  "run; a silent 0.0 is never rendered).", "",
                  "| group | algorithm | backend | shape | µs/call "
                  "| device µs/step | decisions/s |",
                  "|---|---|---|---|---:|---:|---:|"]
        for r in doc["matrix"]:
            if "device_us" not in r:
                dev = ""  # not a measured column for this shape
            else:
                dev = r["device_us"] if r["device_us"] else "n/a"
            lines.append(
                f"| {r['group']} | {r['algorithm']} | {r['backend']} | "
                f"{r['shape']} | {r['us_per_call']} | {dev} | "
                f"{r['decisions_per_sec']:,} |")
        lines.append("")
    if "configs" in doc:
        lines += ["## BASELINE configs", ""]
        for c in doc["configs"]:
            lines.append(f"### Config {c['config']}")
            lines.append("")
            for k, v in c.items():
                if k != "config":
                    lines.append(f"- {k}: {v}")
            lines.append("")
    if "multichip_scaling" in doc:
        lines += _render_multichip(doc["multichip_scaling"],
                                   doc.get("route_phase_us"))
    if "e2e" in doc:
        lines += ["## End-to-end serving (string keys over the wire)", "",
                  "| variant | decisions/s | scalar p50 ms | scalar p99 ms "
                  "| conns×inflight |",
                  "|---|---:|---:|---:|---|"]
        for r in doc["e2e"]:
            if "error" in r:
                lines.append(f"| {r['variant']} | error: {r['error']} | | | |")
            else:
                p50 = r.get("scalar_p50_ms", r.get("frame_p50_ms", "-"))
                p99 = r.get("scalar_p99_ms", r.get("frame_p99_ms", "-"))
                lines.append(
                    f"| {r['variant']} | {r['decisions_per_sec']:,} | "
                    f"{p50} | {p99} | "
                    f"{r['connections']}×{r['inflight_per_conn']} |")
        lines.append("")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmarks")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes, CI-friendly")
    ap.add_argument("--only", choices=["matrix", "configs", "e2e"],
                    default=None)
    ap.add_argument("--out", default=os.path.join(HERE, "RESULTS"))
    ap.add_argument("--trace-sample", type=int, default=0, metavar="N",
                    help="e2e loadgen: sample every Nth frame per "
                         "connection with a wire trace id and record "
                         "client spans (ADR-014; 0 = off)")
    ap.add_argument("--multichip", default=None, metavar="PATH",
                    help="render an existing MULTICHIP_rXX.json (or any "
                         "JSON with a multichip_scaling block) as the "
                         "markdown scaling table to stdout — including "
                         "the collective-vs-host router columns "
                         "(ADR-024; n/a-safe for runs without them) — "
                         "and exit without measuring anything")
    args = ap.parse_args()

    if args.multichip:
        with open(args.multichip) as f:
            blob = json.load(f)
        ms = blob.get("multichip_scaling", blob)
        print("\n".join(_render_multichip(ms, blob.get("route_phase_us"))))
        return

    import jax

    t_start = time.time()
    # --only merges into an existing results file (other groups' data is
    # preserved) so one group can be re-run without redoing the suite.
    doc: dict = {}
    if args.only and os.path.exists(f"{args.out}.json"):
        with open(f"{args.out}.json") as f:
            doc = json.load(f)
    doc["meta"] = {
        "timestamp": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "jax_platform": jax.devices()[0].platform,
        "device_count": len(jax.devices()),
        "python": _platform.python_version(),
        "quick": args.quick,
    }

    def log(msg: str) -> None:
        print(f"[{time.time() - t_start:7.1f}s] {msg}", flush=True)

    if args.only in (None, "matrix"):
        from benchmarks.matrix import run_matrix

        doc["matrix"] = run_matrix(quick=args.quick, log=log)
    if args.only in (None, "configs"):
        from benchmarks.configs import run_configs

        doc["configs"] = run_configs(quick=args.quick, log=log)
    if args.only in (None, "e2e"):
        from benchmarks.e2e import run_e2e

        doc["e2e"] = run_e2e(quick=args.quick,
                             trace_sample=args.trace_sample, log=log)

    doc["meta"]["wall_seconds"] = round(time.time() - t_start, 1)
    with open(f"{args.out}.json", "w") as f:
        json.dump(doc, f, indent=1)
    with open(f"{args.out}.md", "w") as f:
        f.write(_render_md(doc))
    log(f"wrote {args.out}.json / .md")


if __name__ == "__main__":
    main()
