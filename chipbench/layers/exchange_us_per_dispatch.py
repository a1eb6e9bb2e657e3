"""Device time of the two exchanges (rows out to their owning slices,
verdicts back) per dispatch and chip: the trace's ``all-to-all`` op
groups, summed over the chips, over the executions of the step module,
which the trace also counts once per chip. The v5e's trace names the op
``all_to_all`` (my chip run, PR 27), HLO text ``all-to-all``, and a
compiler that makes it asynchronous ``-start`` / ``-done``: all are
taken. From the device planes, never a host timer. None without a
trace, and None when no such group is among the ten op groups
chipbench/trace_reduce.py keeps (the exchange is then under the
tenth-largest group's time; ``breakdown.device_ops`` shows that bound)."""

from chipbench.layers import _collective

META = {"name": "exchange_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": _collective.LAYER, "moves": "decisions_per_s",
        "source": "device_trace",
        "applies": _collective.collective_closed}


def read(sources: dict):
    trace = sources.get("trace")
    if not trace or not trace.get("step") or not trace["step"]["executions"]:
        return None
    rows = [s for name, s in trace.get("device_ops") or []
            if name.replace("_", "-").startswith("all-to-all")]
    if not rows:
        return None
    return sum(rows) / trace["step"]["executions"] * 1e6
