"""Resolve returned -> replies handed to the responder: flight-recorder
stage ``complete``. A host timer."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "complete_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "resolve / pack", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "complete")
