"""Reclaim passes a second: Δ``rate_limiter_directory_reclaim_passes_
total`` between the window's two scrapes ÷ the seconds between them. A
count. About one a second on a table sized for the keys of two windows;
0.0 where no entry goes idle inside a run; eight a second (one in every
eighth of a window) on a table too small for its live keys, which
``directory_unplaced_pct`` then shows. None where the program exports no
such family."""

from chipbench.layers import _reclaim

META = {"name": "reclaim_passes_per_s", "unit": "passes/s", "better": "lower",
        "layer": _reclaim.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _reclaim.applies}


def read(sources: dict):
    n = _reclaim.passes(sources)
    if n is None or not sources.get("scrape_s"):
        return None
    return n / sources["scrape_s"]
