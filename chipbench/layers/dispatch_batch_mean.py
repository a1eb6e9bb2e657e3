"""Decisions per device dispatch, as the coalescer cut them."""

from chipbench import promtext
from chipbench.layers import closed_loop

META = {"name": "dispatch_batch_mean", "unit": "decisions",
        "better": "higher", "layer": "batcher / staging",
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": closed_loop}


def read(sources: dict):
    return promtext.mean_of(sources["metrics_start"], sources["metrics_end"],
                            "rate_limiter_server_batch_size")
