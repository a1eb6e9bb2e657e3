"""What a launch waits for one reclaim pass, on the host's clock:
Δ``rate_limiter_directory_reclaim_seconds_total`` ÷ Δ``…reclaim_passes_
total`` between the window's two scrapes, in microseconds. The counter is
the ``reclaim`` span's own stamps — the pass enqueued behind the steps in
flight, its count fetched, all under the lane's lock on the dispatcher's
thread — so it holds the in-flight steps' time too
(``reclaim_us_per_pass`` is the pass alone, on the device). None without
a pass between the scrapes."""

from chipbench.layers import _reclaim

META = {"name": "reclaim_stall_us_per_pass", "unit": "us", "better": "lower",
        "layer": _reclaim.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _reclaim.applies}


def read(sources: dict):
    seconds = _reclaim.per_pass(sources, "reclaim_seconds_total")
    return None if seconds is None else seconds * 1e6
