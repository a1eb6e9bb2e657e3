"""Coalescing wait: flight-recorder stage ``io`` (frame enqueued ->
drained into a dispatch), mean per dispatch. A host timer."""

from chipbench.layers import open_loop, stage_us

META = {"name": "wait_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "batcher / staging", "moves": "latency_p50_ms",
        "source": "program_span", "applies": open_loop}


def read(sources: dict):
    return stage_us(sources, "io")
