"""Device time of one reclaim pass: the seconds of the trace's
``jit_dense_reclaim`` module ÷ its executions, from the device planes
(never a host timer). None without a trace, or where the capture's five
seconds hold no pass."""

from chipbench.layers import _reclaim

META = {"name": "reclaim_us_per_pass", "unit": "us", "better": "lower",
        "layer": _reclaim.LAYER, "moves": "decisions_per_s",
        "source": "device_trace", "applies": _reclaim.applies}


def read(sources: dict):
    return _reclaim.device_us(sources)
