"""The ``dispatch`` stage from the door's own always-on sums, every
dispatch between the two scrapes counted (``dispatch_us_per_dispatch``
is the mean over the flight recorder's ring, a sample of the window):
rate_limiter_door_stage_seconds_total{stage="dispatch"} over
rate_limiter_door_dispatches_total. None where the program exports
neither."""

from chipbench import promtext
from chipbench.layers import closed_loop

META = {"name": "dispatch_us_exact", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_counter", "applies": closed_loop}


def read(sources: dict):
    a, b = sources["metrics_start"], sources["metrics_end"]
    dispatches = promtext.delta(a, b, "rate_limiter_door_dispatches_total")
    if dispatches <= 0:
        return None
    seconds = promtext.delta(a, b, "rate_limiter_door_stage_seconds_total",
                             stage="dispatch")
    return seconds / dispatches * 1e6
