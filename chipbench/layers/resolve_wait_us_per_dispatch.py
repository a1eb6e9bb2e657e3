"""Host blocked on the oldest in-flight dispatch: flight-recorder stage
``device``. A host timer, named for what it is."""

from chipbench.layers import open_loop, stage_us

META = {"name": "resolve_wait_us_per_dispatch", "unit": "us",
        "better": "lower", "layer": "resolve / pack",
        "moves": "latency_p50_ms", "source": "program_span",
        "applies": open_loop}


def read(sources: dict):
    return stage_us(sources, "device")
