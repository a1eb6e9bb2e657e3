"""How many tables the program holds at once: the device's peak bytes in
use over the resident state's bytes, both in the window's closing scrape
(``rate_limiter_device_peak_bytes`` ÷
``rate_limiter_state_resident_bytes``, each summed over the devices that
hold state). 1.0 = the chip never held more than the state; every copy
of a table a program makes beside the donated one, the tiles' padding
and what else the process keeps on the device (staging, results,
programs) add to it. A deployment sizes ``--dense-capacity`` against
HBM by this multiple. None where the program lacks either gauge (before
PR 42) or the platform reports no memory statistics."""

from chipbench.layers import _memory

META = {"name": "device_peak_over_state", "unit": "x", "better": "lower",
        "layer": _memory.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _memory.applies}


def read(sources: dict):
    resident = _memory.gauge(sources, _memory.RESIDENT)
    peak = _memory.gauge(sources, _memory.PEAK)
    if not resident or peak is None:
        return None
    return peak / resident
