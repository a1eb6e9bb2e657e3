"""Keys the directory inserted a dispatch: the difference of
``rate_limiter_directory_inserts_total`` (distinct never-seen keys, each
claiming an entry inside the decision step) over the difference of
``rate_limiter_door_dispatches_total``. Starts near the batch size on a
fresh server and falls as the population fills in; neither end is
better. None where the program exports no such counter or the door
completed nothing between the scrapes."""

from chipbench import promtext
from chipbench.layers import _directory

META = {"name": "directory_inserts_per_dispatch", "unit": "keys/dispatch",
        "better": "lower", "layer": _directory.LAYER,
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": _directory.dense_closed}


def read(sources: dict):
    inserts = _directory.moved(sources, "inserts_total")
    if inserts is None:
        return None
    dispatches = promtext.delta(sources["metrics_start"],
                                sources["metrics_end"],
                                "rate_limiter_door_dispatches_total")
    if dispatches <= 0:
        return None
    return inserts / dispatches
