"""Share of the collective router's dispatches that it gave back to the
host router (bin overflow, or the strict gate): the difference of
``rate_limiter_collective_fallbacks_total`` (every ``reason``) over the
difference of ``rate_limiter_collective_dispatches_total``, times 100.
Above ~1 % the cell is measuring the host router, not the exchange.
None where the program exports neither counter (before PR 27) or
launched nothing between the scrapes."""

from chipbench import promtext
from chipbench.layers import _collective

META = {"name": "collective_fallback_pct", "unit": "%", "better": "lower",
        "layer": _collective.LAYER, "moves": "decisions_per_s",
        "source": "program_counter",
        "applies": _collective.collective_closed}


def read(sources: dict):
    a, b = sources["metrics_start"], sources["metrics_end"]
    # A fallback is counted under both: it was launched, then decided
    # again by the host router.
    launched = promtext.delta(a, b,
                              "rate_limiter_collective_dispatches_total")
    if launched <= 0:
        return None
    fell = promtext.delta(a, b, "rate_limiter_collective_fallbacks_total")
    return 100.0 * fell / launched
