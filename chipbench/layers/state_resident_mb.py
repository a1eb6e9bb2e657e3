"""Megabytes (10^6 B) of limiter state resident on the device(s):
``rate_limiter_state_resident_bytes`` in the window's closing scrape,
summed over devices — the ``nbytes`` of the state leaves the decision
step is handed (the dense columns and the directory's keys: 32 B an
entry + the padding slot's row), reckoned by the program from the
leaves' shapes and dtypes when it builds the state. What the
configuration's ``device_bytes`` reckons, read off the served surface.
None where the program exports no such gauge (before PR 42)."""

from chipbench.layers import _memory

META = {"name": "state_resident_mb", "unit": "MB", "better": "lower",
        "layer": _memory.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _memory.applies}


def read(sources: dict):
    resident = _memory.gauge(sources, _memory.RESIDENT)
    return None if resident is None else resident / 1e6
