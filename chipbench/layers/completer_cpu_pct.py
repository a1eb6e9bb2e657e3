"""The share of its wall the completer thread computes: 100 x
``rate_limiter_door_thread_cpu_seconds_total{thread="completer"}`` over
the sum of its ``…thread_seconds_total`` states, both between the
scrapes (_threads.py). The completer waits by design (``idle``, the
device, the transfer), so the share is small; what it computes is the
NumPy rebuild and the result's packing. None before PR 37."""

from chipbench.layers import _threads, closed_loop

META = {"name": "completer_cpu_pct", "unit": "%", "better": "higher",
        "layer": "resolve / pack", "moves": "decisions_per_s",
        "source": "program_counter", "applies": closed_loop}


def read(sources: dict):
    return _threads.cpu_pct(sources, "completer")
