"""Device buffers resolve asks the device for, per dispatch of the door:
the difference of ``rate_limiter_result_fetches_total`` (one per array
leaf per addressable shard of a dispatch's result, counted by the
program, every shard label summed) over the difference of
``rate_limiter_door_dispatches_total``. 1.0 where the step packs its
result into one buffer; 4 a frame under the collective router on four
chips (a shard a chip). A count; None where the program exports no such
counter (before PR 29) or the door completed nothing between the scrapes."""

from chipbench import promtext
from chipbench.layers import closed_loop

META = {"name": "fetches_per_dispatch", "unit": "1/dispatch",
        "better": "lower", "layer": "resolve / pack",
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": closed_loop}

FETCHES = "rate_limiter_result_fetches_total"


def read(sources: dict):
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not any(name == FETCHES for name, _ in b):
        return None
    dispatches = promtext.delta(a, b, "rate_limiter_door_dispatches_total")
    if dispatches <= 0:
        return None
    return promtext.delta(a, b, FETCHES) / dispatches
