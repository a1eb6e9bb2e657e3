"""Entries a reclaim pass gives up: Δ``rate_limiter_directory_reclaimed_
total`` ÷ Δ``…reclaim_passes_total`` between the window's two scrapes —
the keys not seen for two windows since the pass before. None without a
pass between the scrapes."""

from chipbench.layers import _reclaim

META = {"name": "reclaimed_per_pass", "unit": "entries", "better": "higher",
        "layer": _reclaim.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _reclaim.applies}


def read(sources: dict):
    return _reclaim.per_pass(sources, "reclaimed_total")
