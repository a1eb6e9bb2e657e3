"""Inside the dispatch stage: staging slot, pad copies, lock waits and the
rollover check, up to where the step's operands are placed.
Flight-recorder stage ``prep``; None where the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "prep_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "prep")
