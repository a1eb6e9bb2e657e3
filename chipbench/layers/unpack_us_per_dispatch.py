"""The resolve's NumPy rebuild: ``np.asarray`` returned ->
``BatchResult``'s columns built (``unpack_window`` / ``unpack_bucket`` on
``[:b]``), on the resolving thread. Flight-recorder stage ``unpack``
(PR 37): the second half of what ``fetch`` was until then, so ``fetch``
now ends where the buffer is on the host and the GIL is back. None where
the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "unpack_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "resolve / pack", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "unpack")
