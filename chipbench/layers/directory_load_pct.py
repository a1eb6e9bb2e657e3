"""Share of the directory's entries that hold a key at the window's end:
100 x ``rate_limiter_directory_entries`` over
``rate_limiter_directory_capacity`` in the closing scrape (the host's
count: inserts reported at resolve, less what reclaim and reset freed).
Probe lengths and unplaced rows follow it; the configuration sizes the
table for about one half. None where the program exports no such
gauges."""

from chipbench import promtext
from chipbench.layers import _directory

META = {"name": "directory_load_pct", "unit": "%", "better": "lower",
        "layer": _directory.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _directory.dense_closed}


def read(sources: dict):
    end = sources["metrics_end"]
    if not _directory.has(end, "capacity"):
        return None
    capacity = promtext.total(end, _directory.FAMILY + "capacity")
    if capacity <= 0:
        return None
    return 100.0 * promtext.total(end, _directory.FAMILY + "entries") \
        / capacity
