"""Inside the dispatch stage: last line of the Python launch callback ->
the ticket pushed in C++ (the GIL's release, the wait for a slot of the
in-flight window, the push). Long only when the completer, not the
dispatcher, holds the pipeline back. Flight-recorder stage ``leave``;
None where the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "leave_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "leave")
