"""Operand shards the collective router's launch sends to the devices,
per frame it launched: the difference of
``rate_limiter_collective_placements_total`` (one per array operand per
addressable shard, counted by the program beside its dispatches; a host
scalar handed to the jitted call counts as the shards ``jit`` makes of
it) over the difference of ``rate_limiter_collective_dispatches_total``.
The device count where the frame leaves the host as ONE staged buffer, a
row a device (4.0 on four chips, since PR 45); two sharded columns and
two replicated scalars were 16. A count; None where the program exports
no such counter (before PR 45) or launched nothing between the scrapes."""

from chipbench import promtext
from chipbench.layers import _collective

META = {"name": "collective_placements_per_dispatch", "unit": "1/dispatch",
        "better": "lower", "layer": _collective.LAYER,
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": _collective.collective_closed}

PLACEMENTS = "rate_limiter_collective_placements_total"


def read(sources: dict):
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not any(name == PLACEMENTS for name, _ in b):
        return None
    launched = promtext.delta(a, b,
                              "rate_limiter_collective_dispatches_total")
    if launched <= 0:
        return None
    return promtext.delta(a, b, PLACEMENTS) / launched
