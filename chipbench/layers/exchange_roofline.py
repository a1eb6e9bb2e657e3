"""The two exchanges' share of the inter-chip link: the bytes one chip
must put on ICI per dispatch (chipbench/bytes_ici.py) over the chip's
published ICI rate (chipbench/peaks_ici.json), divided by the device
time the ``all-to-all`` ops took per dispatch and chip. Bound by bytes
on paper; in fact a 4,096-id frame puts ~15 KB on a chip's links, which
200 GB/s moves in under 0.1 us, so the exchange is bound by the
collective's latency and this share reads well under 1 %: it says how
far a dispatch is from the size at which the links would matter, not
that the links are slow. None without a trace, a device in the table,
or an ``all-to-all`` group among those the reducer keeps."""

import json
import os

from chipbench import bytes_ici
from chipbench.layers import _collective, dispatch_batch_mean
from chipbench.layers import exchange_us_per_dispatch

META = {"name": "exchange_roofline", "unit": "%", "better": "higher",
        "layer": _collective.LAYER, "moves": "decisions_per_s",
        "source": "device_trace",
        "applies": _collective.collective_closed}

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ici_peak(peaks):
    """The peaks_ici.json row of the device whose peaks.json row the
    harness handed over (it passes the row, not the kind), or None."""
    if not peaks:
        return None
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        kinds = [k for k, row in json.load(fh).items() if row == peaks]
    with open(os.path.join(_HERE, "peaks_ici.json")) as fh:
        table = json.load(fh)
    return table.get(kinds[0]) if kinds else None


def read(sources: dict):
    exchange_us = exchange_us_per_dispatch.read(sources)
    batch = dispatch_batch_mean.read(sources)
    peak = ici_peak(sources.get("peaks"))
    if not exchange_us or not batch or not peak:
        return None
    cell = sources["cell"]
    must = bytes_ici.exchange_bytes(cell["config"]["algorithm"],
                                    batch / cell["chips"], cell["chips"])
    return 100.0 * (must / peak["ici_bytes_per_s"]) / (exchange_us * 1e-6)
