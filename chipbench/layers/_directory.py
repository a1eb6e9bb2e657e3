"""What the key-directory readers share: the layer's name as PERF.md's
list has it, the cells they apply to, the counter families
(``rate_limiter_directory_*``, exported at scrape by the program's
MetricsDecorator from ``DenseLimiter.directory_stats``: the decision
step's own tail words, summed at resolve — counts, recorder on or off)."""

from chipbench import promtext
from chipbench.layers import closed_loop

LAYER = "directory"
FAMILY = "rate_limiter_directory_"


def dense(cell: dict) -> bool:
    """The configuration's ``--backend`` is ``dense``."""
    flags = cell["config"]["server_flags"]
    return any(a == "--backend" and b == "dense"
               for a, b in zip(flags, flags[1:]))


def dense_closed(cell: dict) -> bool:
    return dense(cell) and closed_loop(cell)


def has(samples: dict, name: str) -> bool:
    return any(family == FAMILY + name for family, _ in samples)


def moved(sources: dict, name: str):
    """The counter's difference between the window's two scrapes; None
    where the program exports no such family (before PR 33)."""
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not has(b, name):
        return None
    return promtext.delta(a, b, FAMILY + name)
