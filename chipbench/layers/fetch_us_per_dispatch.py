"""The completer's device→host fetch: from the dispatch's result being
ready on the device to BatchResult's NumPy columns built — one packed
int32 buffer a device fetched in one call, then the 64-bit and float
columns rebuilt on the host (PR 29; before, four array leaves fetched one
by one, seven device buffers on a TPU). Flight-recorder stage ``fetch``,
recorded on the resolving thread, once per resolved ticket (the collective
router: once per frame); None where the program has no such span."""

from chipbench.layers import closed_loop, stage_us

META = {"name": "fetch_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "resolve / pack", "moves": "decisions_per_s",
        "source": "program_span", "applies": closed_loop}


def read(sources: dict):
    return stage_us(sources, "fetch")
