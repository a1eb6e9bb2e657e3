"""Seconds the server spent prewarming pad shapes (compiling, on the
first run of a checkout): its ``prewarmed pad shapes ... in Xs`` lines."""

import re

_PREWARM = re.compile(r"prewarmed pad shapes .* in ([\d.]+)s")

META = {"name": "prewarm_s", "unit": "s", "better": "lower",
        "layer": "set-up", "moves": "setup_s", "source": "program_span",
        "applies": lambda cell: True}


def read(sources: dict):
    found = _PREWARM.findall(sources.get("server_log") or "")
    return sum(float(s) for s in found) if found else None
