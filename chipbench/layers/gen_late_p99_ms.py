"""How late the generator itself sent: send instant less due instant,
p99 over the window. A starved generator voids the cell's latencies."""

from chipbench.layers import open_loop

META = {"name": "gen_late_p99_ms", "unit": "ms", "better": "lower",
        "layer": "client", "moves": "latency_p99_ms",
        "source": "program_counter", "applies": open_loop}


def read(sources: dict):
    late = sources["loadgen"]["gen_late_ms"]
    return late["p99"] if late["n"] else None
