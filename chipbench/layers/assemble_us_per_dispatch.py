"""Inside the collective launch: the per-slice state leaves gathered into
global sharded arrays (``jax.make_array_from_single_device_arrays`` per
mutable leaf, an eager ``reshape(1)`` per scalar leaf per slice) — host
work no other path has. Flight-recorder stage ``assemble``; None where
the program has no such span (before PR 27)."""

from chipbench.layers import _collective, stage_us

META = {"name": "assemble_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": _collective.LAYER, "moves": "decisions_per_s",
        "source": "program_span",
        "applies": _collective.collective_closed}


def read(sources: dict):
    return stage_us(sources, "assemble")
