"""Share of the door's dispatches whose device step ran the per-key
override lookup: 100 x the difference of
``rate_limiter_override_lookup_dispatches_total`` (dispatches launched
while the override table held an entry, counted by the program under the
launch's own lock, every shard label summed; a collective frame counts
once) over the difference of ``rate_limiter_door_dispatches_total``. The
step branches on the table's occupancy (PR 31): at 0.0 no dispatch of the
window paid for the binary search, at 100.0 every one did. A count; None
where the program exports no such counter (before PR 31) or the door
completed nothing between the scrapes."""

from chipbench import promtext
from chipbench.layers import closed_loop

META = {"name": "override_lookup_pct", "unit": "%",
        "better": "lower", "layer": "device step",
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": closed_loop}

LOOKUPS = "rate_limiter_override_lookup_dispatches_total"


def read(sources: dict):
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not any(name == LOOKUPS for name, _ in b):
        return None
    dispatches = promtext.delta(a, b, "rate_limiter_door_dispatches_total")
    if dispatches <= 0:
        return None
    return 100.0 * promtext.delta(a, b, LOOKUPS) / dispatches
