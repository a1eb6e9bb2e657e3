"""Decisions sent per owning slice, max over mean (the generator carries
the door's splitmix64 routing bit for bit)."""

from chipbench.layers import closed_loop

META = {"name": "slice_imbalance", "unit": "ratio", "better": "lower",
        "layer": "mesh routing", "moves": "decisions_per_s",
        "source": "program_counter",
        "applies": lambda cell: cell["chips"] > 1 and closed_loop(cell)}


def read(sources: dict):
    sent = sources["loadgen"].get("slice_sent") or []
    if len(sent) < 2 or not sum(sent):
        return None
    return max(sent) / (sum(sent) / len(sent))
