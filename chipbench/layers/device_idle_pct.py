"""Share of the traced window in which no operation ran on the device:
1 - union of device-op intervals / window; the mean over chips."""

from chipbench.layers import closed_loop

META = {"name": "device_idle_pct", "unit": "%", "better": "lower",
        "layer": "device", "moves": "decisions_per_s",
        "source": "device_trace", "applies": closed_loop}


def read(sources: dict):
    trace = sources.get("trace")
    return trace.get("idle_pct") if trace else None
