"""How much of the ``dispatch`` stage is under a span once the launch
callback is tiled: ``dispatch_covered_pct``'s sum (enter + hash + prep +
place + assemble + step + writeback + finish + leave) + ``descend``
(callback's first line -> where ``prep`` opens) + ``ascend`` (where
``finish`` closed -> callback's last line), over ``dispatch``, means per
dispatch. What is left is the hand-over between C++ and Python: the
argument build, the call, the ticket's way back. None where the program
has no ``descend`` / ``ascend`` spans (before PR 37)."""

from chipbench.layers import closed_loop, dispatch_covered_pct, stage_us

META = {"name": "dispatch_accounted_pct", "unit": "%", "better": "higher",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    covered = dispatch_covered_pct.read(sources)
    whole = stage_us(sources, "dispatch")
    tiles = [stage_us(sources, stage) for stage in ("descend", "ascend")]
    if covered is None or not whole or None in tiles:
        return None
    return covered + 100.0 * sum(tiles) / whole
