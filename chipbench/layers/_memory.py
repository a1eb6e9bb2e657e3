"""What the device-memory readers share: the gauges every server with a
device backend exports at scrape since PR 42 —
``rate_limiter_state_resident_bytes{shard,device}`` (the bytes of the
limiter's state leaves on that device, reckoned from their shapes and
dtypes when the state is built) and
``rate_limiter_device_peak_bytes{device}``
(``device.memory_stats()["peak_bytes_in_use"]`` of each device that
holds state, read when ``/metrics`` is; no sample where the platform
gives none, as on the CPU) — and the cells they apply to: where a
table is sized against HBM, the dense backend's
(``chipbench/README-memory.md``)."""

from chipbench import promtext
from chipbench.layers import _directory

LAYER = "device"
RESIDENT = "rate_limiter_state_resident_bytes"
PEAK = "rate_limiter_device_peak_bytes"
applies = _directory.dense_closed


def gauge(sources: dict, family: str):
    """The gauge in the window's closing scrape, summed over devices (and
    dispatch shards); None where the scrape has no sample of the family
    (a program before PR 42; the peak on a platform without memory
    statistics)."""
    end = sources["metrics_end"]
    if not any(name == family for name, _ in end):
        return None
    return promtext.total(end, family)
