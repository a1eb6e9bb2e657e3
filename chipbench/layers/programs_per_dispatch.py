"""Device programs executed per dispatch: executions of every module on
the trace's ``XLA Modules`` lines over executions of the step module
(the one with the most device time). 1.0 is one launch a dispatch; the
rotation adds one a second. A count from the device trace; None without
a trace."""

from chipbench.layers import closed_loop

META = {"name": "programs_per_dispatch", "unit": "1/dispatch",
        "better": "lower", "layer": "dispatch", "moves": "decisions_per_s",
        "source": "device_trace", "applies": closed_loop}


def read(sources: dict):
    trace = sources.get("trace")
    if not trace or not trace.get("step") or not trace["step"]["executions"]:
        return None
    launched = sum(row[1] for row in trace.get("modules") or [])
    return launched / trace["step"]["executions"]
