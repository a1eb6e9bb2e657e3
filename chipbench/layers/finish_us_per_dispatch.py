"""Inside the dispatch stage: after the step call — host-side limits, the
finish and pack programs enqueued, the ticket filled. Flight-recorder
stage ``finish``; None where the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "finish_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "finish")
