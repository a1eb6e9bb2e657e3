"""The reclaim pass's share of its roofline: the bytes one pass must move
(chipbench/bytes_reclaim.py ``pass_bytes``: every entry's stamp and key
read, the entries it gives up written back — ``reclaimed_per_pass`` of
them, from the counters) over the peak HBM rate (chipbench/peaks.json),
divided by the device time one pass took (``reclaim_us_per_pass``, from
the trace). Bound by bytes. None without a pass in the capture, without a
pass between the scrapes, or on a configuration whose byte model counts
no pass."""

from chipbench import bytes as need
from chipbench.layers import _reclaim, reclaimed_per_pass

META = {"name": "reclaim_roofline", "unit": "%", "better": "higher",
        "layer": _reclaim.LAYER, "moves": "decisions_per_s",
        "source": "device_trace", "applies": _reclaim.applies}


def read(sources: dict):
    pass_us = _reclaim.device_us(sources)
    freed = reclaimed_per_pass.read(sources)
    peaks = sources.get("peaks")
    cfg = sources["cell"]["config"]
    model = need.model_of(cfg)
    if not pass_us or freed is None or not peaks \
            or not hasattr(model, "pass_bytes"):
        return None
    must = model.pass_bytes(cfg, freed)
    return 100.0 * (must / peaks["hbm_bytes_per_s"]) / (pass_us * 1e-6)
