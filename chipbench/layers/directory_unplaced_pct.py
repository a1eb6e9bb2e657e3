"""Share of the window's decisions whose key found no directory entry
within the probe bound and were answered by the fail-open / fail-closed
policy: 100 x the difference of ``rate_limiter_directory_unplaced_total``
over the difference of ``rate_limiter_directory_lookups_total`` (every
decision row is one lookup). Above 0 the cell measures the policy, not
the rule — as ``collective_fallback_pct`` does for the host router; the
run's own check then counts the same rows as policy answers. None where
the program exports no such counters or decided nothing between the
scrapes."""

from chipbench.layers import _directory

META = {"name": "directory_unplaced_pct", "unit": "%", "better": "lower",
        "layer": _directory.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _directory.dense_closed}


def read(sources: dict):
    lookups = _directory.moved(sources, "lookups_total")
    unplaced = _directory.moved(sources, "unplaced_total")
    if not lookups or unplaced is None or lookups <= 0:
        return None
    return 100.0 * unplaced / lookups
