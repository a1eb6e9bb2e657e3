"""Share of the FULLEST slice's directory that holds a key at the
window's end: the largest, over the ``shard`` labels of the closing
scrape, of 100 x ``rate_limiter_directory_entries`` over
``rate_limiter_directory_capacity``. Under hash ownership every chip's
``--dense-capacity`` has to be sized for the slice that fills first;
``directory_load_pct`` is the host's total (the mean over equal
slices). None where the program exports no such gauges."""

from chipbench.layers import _directory

META = {"name": "directory_load_max_pct", "unit": "%", "better": "lower",
        "layer": _directory.LAYER, "moves": "decisions_per_s",
        "source": "program_counter",
        "applies": lambda cell: (_directory.dense_closed(cell)
                                 and cell["chips"] > 1)}


def read(sources: dict):
    by_shard: dict = {}
    for (family, labels), value in sources["metrics_end"].items():
        if family.startswith(_directory.FAMILY):
            name = family[len(_directory.FAMILY):]
            if name in ("entries", "capacity"):
                by_shard.setdefault(dict(labels).get("shard"), {})[name] = \
                    value
    loads = [100.0 * s["entries"] / s["capacity"] for s in by_shard.values()
             if s.get("capacity", 0) > 0 and "entries" in s]
    return max(loads) if loads else None
