"""Inside the dispatch stage: the jitted step call returning — the
enqueue, not the execution (that is ``device_us_per_dispatch``).
Flight-recorder stage ``step``; None where the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "step_enqueue_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "step")
