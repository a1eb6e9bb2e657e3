"""Busy share of the traced window on the BUSIEST chip: the largest of
100 - ``idle_pct`` over the capture's device planes
(``trace["devices"]``, chipbench/trace_reduce.py). Behind the host
router the chip that owns the hottest ids decides the most rows, and it
is the one that will pace the cell once the host stops doing so;
``device_idle_pct`` is the mean over chips. None without a capture."""

from chipbench.layers import _directory

META = {"name": "device_busy_max_pct", "unit": "%", "better": "lower",
        "layer": "device", "moves": "decisions_per_s",
        "source": "device_trace",
        "applies": lambda cell: (_directory.dense_closed(cell)
                                 and cell["chips"] > 1)}


def read(sources: dict):
    trace = sources.get("trace")
    idle = [d["idle_pct"] for d in (trace or {}).get("devices") or []
            if d.get("idle_pct") is not None]
    return 100.0 - min(idle) if idle else None
