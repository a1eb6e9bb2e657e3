"""Inside the dispatch stage: the group drained in C++ -> first line of
the Python launch callback (column gather + the wait for the GIL).
Flight-recorder stage ``enter``; None where the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "enter_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "enter")
