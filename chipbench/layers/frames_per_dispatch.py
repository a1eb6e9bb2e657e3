"""Wire frames the door's coalescer put into one device dispatch: the
difference of ``rate_limiter_door_frames_total`` (``Pending``s drained
into decision dispatches — a frame, or the part of one that a dispatch
took: a carved head counts once in its dispatch and the rest of the
frame once in the next) over the difference of
``rate_limiter_door_dispatches_total``. 16.0 where every dispatch is
sixteen whole 4,096-id frames under ``--max-batch 65536``; near 1 the
generator, the io threads or the coalescing wait feed the dispatcher a
frame at a time. A count; None where the program exports no such counter
(before PR 35) or the door completed nothing between the scrapes."""

from chipbench import promtext
from chipbench.layers import _coalesce

META = {"name": "frames_per_dispatch", "unit": "frames/dispatch",
        "better": "higher", "layer": _coalesce.LAYER,
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": _coalesce.coalescing}


def read(sources: dict):
    frames = _coalesce.moved(sources, _coalesce.FRAMES)
    dispatches = promtext.delta(sources["metrics_start"],
                                sources["metrics_end"],
                                "rate_limiter_door_dispatches_total")
    if frames is None or dispatches <= 0:
        return None
    return frames / dispatches
