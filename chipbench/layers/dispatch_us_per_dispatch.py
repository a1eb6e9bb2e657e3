"""Host time to hash, stage and enqueue one dispatch: flight-recorder
stage ``dispatch`` (drained -> launch returned). A host timer."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "dispatch_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "dispatch")
