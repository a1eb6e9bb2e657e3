"""Device-busy time per execution of the step program, both from the
device planes of the trace (never from a host timer)."""

from chipbench.layers import closed_loop

META = {"name": "device_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "device step", "moves": "decisions_per_s",
        "source": "device_trace", "applies": closed_loop}


def read(sources: dict):
    trace = sources.get("trace")
    if not trace or not trace.get("step") or not trace["step"]["executions"]:
        return None
    busy_s = trace["busy_s"] * trace["n_devices"]
    return busy_s / trace["step"]["executions"] * 1e6
