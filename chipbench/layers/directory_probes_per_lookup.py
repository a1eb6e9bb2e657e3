"""Directory buckets examined a decision row: the difference of
``rate_limiter_directory_probes_total`` over the difference of
``rate_limiter_directory_lookups_total`` between the window's scrapes.
The step sums, over its rows, the bucket rows its probe loop read (a
retry after a lost claim reads its bucket again) and brings the sum home
in the packed result's tail words. 1.0: every key sat in its home
bucket; above ~1.2 buckets overflow. None where the program exports no
such counters or looked nothing up between the scrapes."""

from chipbench.layers import _directory

META = {"name": "directory_probes_per_lookup", "unit": "probes/lookup",
        "better": "lower", "layer": _directory.LAYER,
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": _directory.dense_closed}


def read(sources: dict):
    lookups = _directory.moved(sources, "lookups_total")
    probes = _directory.moved(sources, "probes_total")
    if not lookups or probes is None or lookups <= 0:
        return None
    return probes / lookups
