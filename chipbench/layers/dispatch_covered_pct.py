"""How much of the ``dispatch`` stage its sub-stage spans account for:
(enter + hash + prep + place + assemble + step + writeback + finish +
leave) / dispatch, means per dispatch. What is left is Python of the
launch callback outside the limiter: its bookkeeping and the decorators'
delegation. ``hash`` exists on the string lane only, ``assemble`` and
``writeback`` in the collective router's launch only (between ``place``
and ``step``, and between ``step`` and ``finish``; left out until PR 32);
None where the program has no sub-stage spans."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "dispatch_covered_pct", "unit": "%", "better": "higher",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}

_REQUIRED = ("enter", "prep", "place", "step", "finish", "leave")
_WHERE_RECORDED = ("hash", "assemble", "writeback")


def read(sources: dict):
    whole = stage_us(sources, "dispatch")
    parts = [stage_us(sources, stage) for stage in _REQUIRED]
    if not whole or None in parts:
        return None
    extra = sum(stage_us(sources, stage) or 0.0 for stage in _WHERE_RECORDED)
    return 100.0 * (sum(parts) + extra) / whole
