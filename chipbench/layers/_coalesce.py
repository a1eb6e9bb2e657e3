"""What the two coalescing readers share: the cells they apply to and the
door's counters (``rate_limiter_door_frames_total`` and
``rate_limiter_door_carved_frames_total``: C++ atomics over every
dispatch, recorder on or off, exported at scrape beside
``rate_limiter_door_dispatches_total``)."""

from chipbench import promtext
from chipbench.layers import closed_loop

LAYER = "batcher / staging"
FRAMES = "rate_limiter_door_frames_total"
CARVED = "rate_limiter_door_carved_frames_total"
#: The server's own default of ``--max-batch`` (serving/__main__.py).
DEFAULT_MAX_BATCH = 4096


def max_batch(cell: dict) -> int:
    """The configuration's ``--max-batch``, the server's default where
    its flags name none."""
    flags = cell["config"]["server_flags"]
    for flag, value in zip(flags, flags[1:]):
        if flag == "--max-batch":
            return int(value)
    return DEFAULT_MAX_BATCH


def coalescing(cell: dict) -> bool:
    """A closed-loop cell whose dispatches hold more than one frame of
    its traffic: ``--max-batch`` exceeds the mix's ``frame_keys``."""
    return closed_loop(cell) and \
        max_batch(cell) > cell["traffic"]["frame_keys"]


def moved(sources: dict, family: str):
    """The counter's difference between the window's two scrapes; None
    where the program exports no such family (before PR 35)."""
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not any(name == family for name, _ in b):
        return None
    return promtext.delta(a, b, family)
