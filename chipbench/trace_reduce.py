"""From a profiler trace (.xplane.pb) to the numbers the readers use.

Run as a child process, ``python -m chipbench.trace_reduce <xplane.pb>
<out.json>``, pinned to ``JAX_PLATFORMS=cpu`` after the server has
exited: ``jax.profiler.ProfileData`` needs jax imported, and the runner
stays off JAX.

What it reads (a TPU trace has one ``/device:TPU:<n>`` plane per chip):

busy / idle    union of the intervals of the device's ``XLA Ops`` line
               (the module line where a trace has no op line) over the
               traced window: first to last event of the device planes.
step program   the module (digits in brackets stripped) with the most
               device time on the ``XLA Modules`` line; its executions
               are the dispatches the device ran.
device_ops     op time grouped by HLO name with digits stripped.
idle_gaps      each gap between device ops, named by the host event of
               the same trace that covers its middle: the innermost
               TraceMe (``ratelimiter/<algo>/launch``, ``PjitFunction``,
               transfers), else the innermost traced Python call
               (``$file:line fn``), else ``host-outside-jax``.
"""

from __future__ import annotations

import bisect
import json
import re
import sys

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_WALK = 256          # how far back to look for the covering host event
MIN_GAP_NS = 1_000   # gaps under 1 us are the device's own pipelining


def op_group(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``; ``sort.42`` -> ``sort``."""
    head = name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    return re.sub(r"[.\d]+$", "", head) or head


def module_group(name: str) -> str:
    """``jit__step(1234567)`` -> ``jit__step``."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def gaps(busy: list, t0: float, t1: float) -> list:
    """The idle [start, end) intervals of [t0, t1) around ``busy``."""
    out, at = [], t0
    for start, end in busy:
        if start > at:
            out.append((at, min(start, t1)))
        at = max(at, end)
    if t1 > at:
        out.append((at, t1))
    return [(a, b) for a, b in out if b - a >= MIN_GAP_NS]


class HostIndex:
    """Host events by line, for "what covers instant t"."""

    def __init__(self):
        self._lines = []   # (starts, ends, names) sorted by start

    def add_line(self, events: list) -> None:
        events.sort(key=lambda e: e[0])
        self._lines.append(([e[0] for e in events], [e[1] for e in events],
                            [e[2] for e in events]))

    def covering(self, t: float) -> str:
        best = None    # (is python frame, duration, name)
        for starts, ends, names in self._lines:
            i = bisect.bisect_right(starts, t) - 1
            seen_python = False
            for j in range(i, max(-1, i - _WALK), -1):
                if ends[j] <= t:
                    continue
                # Events of a line nest, so the first hit is the innermost;
                # a TraceMe further out still beats a traced Python call.
                python = names[j].startswith("$")
                if python and seen_python:
                    continue
                seen_python = seen_python or python
                cand = (python, ends[j] - starts[j], names[j])
                if best is None or cand < best:
                    best = cand
                if not python:
                    break
        return best[2] if best else "host-outside-jax"


def reduce_planes(planes: list) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(start_ns,
    duration_ns, name), ...]}]}] -> the reduced trace. Kept apart from
    the file reader so that it can be checked on hand-made planes."""
    def span(which):
        events = [e for p in which for ln in p["lines"] for e in ln["events"]]
        return (min((e[0] for e in events), default=0.0),
                max((e[0] + e[1] for e in events), default=0.0))

    # The window is the stretch in which the device was traced: first to
    # last device event. The host planes also hold the profiler's own
    # start and stop (hundreds of ms), in which no device event is taken.
    on_device = [p for p in planes if _DEVICE_PLANE.match(p["name"])]
    t0, t1 = span(on_device or planes)
    host = HostIndex()
    for p in planes:
        if _DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            host.add_line([(s, s + d, n) for s, d, n in ln["events"] if d > 0])

    devices, op_time, gap_time, module_rows = [], {}, {}, {}
    for p in planes:
        if not _DEVICE_PLANE.match(p["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        ops = lines.get(_OPS_LINE) or lines.get(_MODULES_LINE) or []
        busy = union([[s, s + d] for s, d, _ in ops])
        busy_ns = sum(b - a for a, b in busy)
        for s, d, n in lines.get(_OPS_LINE, []):
            g = op_group(n)
            op_time[g] = op_time.get(g, 0.0) + d
        per_module = {}
        for s, d, n in lines.get(_MODULES_LINE, []):
            row = per_module.setdefault(module_group(n), [0, 0.0])
            row[0] += 1
            row[1] += d
        for g, (count, ns) in per_module.items():
            row = module_rows.setdefault(g, [0, 0.0])
            row[0] += count
            row[1] += ns
        idle = gaps(busy, t0, t1)
        for a, b in idle:
            name = host.covering((a + b) / 2)
            gap_time[name] = gap_time.get(name, 0.0) + (b - a)
        devices.append({
            "plane": p["name"], "busy_s": busy_ns / 1e9,
            "idle_pct": 100.0 * (1 - busy_ns / (t1 - t0)) if t1 > t0 else None,
            "ops": len(ops), "gaps": len(idle),
            "longest_gap_s": max((b - a for a, b in idle), default=0.0) / 1e9,
            "modules": {g: {"executions": c, "seconds": ns / 1e9}
                        for g, (c, ns) in per_module.items()}})

    def top(table: dict) -> list:
        return [[name, ns / 1e9] for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    step = max(module_rows.items(), key=lambda kv: kv[1][1], default=None)
    n_dev = len(devices)
    return {
        "window_s": (t1 - t0) / 1e9,
        "n_devices": n_dev,
        "busy_s": sum(d["busy_s"] for d in devices) / n_dev if n_dev else 0.0,
        "idle_pct": (sum(d["idle_pct"] for d in devices) / n_dev
                     if n_dev and t1 > t0 else None),
        "step": ({"module": step[0], "executions": step[1][0],
                  "seconds": step[1][1] / 1e9} if step else None),
        "modules": [[g, c, ns / 1e9] for g, (c, ns) in
                    sorted(module_rows.items(), key=lambda kv: -kv[1][1])],
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
        "devices": devices,
    }


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.start_ns, e.duration_ns, e.name) for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def main() -> int:
    src, dst = sys.argv[1:3]
    reduced = reduce_planes(read_planes(src))
    with open(dst, "w") as fh:
        json.dump(reduced, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
