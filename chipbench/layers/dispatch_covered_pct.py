"""How much of the ``dispatch`` stage its sub-stage spans account for:
(enter + hash + prep + place + step + finish + leave) / dispatch, means
per dispatch. What is left is Python of the launch callback outside the
limiter: its bookkeeping and the decorators' delegation. ``hash`` exists
on the string lane only; None where the program has no sub-stage spans."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "dispatch_covered_pct", "unit": "%", "better": "higher",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}

_REQUIRED = ("enter", "prep", "place", "step", "finish", "leave")


def read(sources: dict):
    whole = stage_us(sources, "dispatch")
    parts = [stage_us(sources, stage) for stage in _REQUIRED]
    if not whole or None in parts:
        return None
    return 100.0 * (sum(parts) + (stage_us(sources, "hash") or 0.0)) / whole
