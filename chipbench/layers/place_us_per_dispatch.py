"""Inside the dispatch stage: host -> device placement of the step's
operands (ids, ns, the clock scalar, the policy table). Flight-recorder
stage ``place``; None where the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "place_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "place")
