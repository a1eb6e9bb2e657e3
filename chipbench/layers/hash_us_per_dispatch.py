"""Inside the dispatch stage, string lane only: bulk-hashing the drained
keys. Flight-recorder stage ``hash``; None where the program has no such
span."""

from chipbench.layers import open_loop, stage_us

META = {"name": "hash_us_per_dispatch", "unit": "us", "better": "lower",
        "layer": "dispatch", "moves": "latency_p50_ms",
        "source": "program_span",
        "applies": lambda cell: (cell["traffic"]["lane"] == "string"
                                 and open_loop(cell))}


def read(sources: dict):
    return stage_us(sources, "hash")
