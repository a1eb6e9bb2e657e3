"""Inside the collective launch: each device's output shard installed as
its slice's state leaf after the step was enqueued (an eager
``reshape(())`` per scalar leaf per slice). Flight-recorder stage
``writeback``; None where the program has no such span (before PR 27)."""

from chipbench.layers import _collective, stage_us

META = {"name": "writeback_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": _collective.LAYER, "moves": "decisions_per_s",
        "source": "program_span",
        "applies": _collective.collective_closed}


def read(sources: dict):
    return stage_us(sources, "writeback")
