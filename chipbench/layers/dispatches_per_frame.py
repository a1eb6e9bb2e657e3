"""Per-slice dispatches one client frame becomes behind the mesh's
router (a count): 1 when a frame stays on its slice, the slice count
when it fans out to all of them."""

from chipbench import promtext
from chipbench.layers import closed_loop

META = {"name": "dispatches_per_frame", "unit": "1/frame", "better": "lower",
        "layer": "mesh routing", "moves": "decisions_per_s",
        "source": "program_counter",
        "applies": lambda cell: cell["chips"] > 1 and closed_loop(cell)}


def read(sources: dict):
    gen = sources["loadgen"]
    dispatches = promtext.delta(sources["metrics_start"],
                                sources["metrics_end"],
                                "rate_limiter_server_batch_size_count")
    if not gen["completed_frames"] or not sources.get("scrape_s"):
        return None
    frames_per_s = gen["completed_frames"] / gen["window_s"]
    return dispatches / sources["scrape_s"] / frames_per_s
