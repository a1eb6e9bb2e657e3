"""Share of the coalescer's frames that were cut at the ``max_batch``
boundary: 100 x the difference of
``rate_limiter_door_carved_frames_total`` (frames whose head filled a
dispatch and whose rest opened the next: each costs a ``BatchJoin`` and
two deposits, and its reply waits for both dispatches) over the
difference of ``rate_limiter_door_frames_total``. 0.0 while runs of whole
frames fill a dispatch exactly (sixteen 4,096-id frames in 65,536); a
probe or control frame in the queue misaligns the run and every dispatch
after it carves one. A count; None where the program exports no such
counters (before PR 35) or the door took no frame between the scrapes."""

from chipbench.layers import _coalesce

META = {"name": "carved_frames_pct", "unit": "%", "better": "lower",
        "layer": _coalesce.LAYER, "moves": "decisions_per_s",
        "source": "program_counter", "applies": _coalesce.coalescing}


def read(sources: dict):
    frames = _coalesce.moved(sources, _coalesce.FRAMES)
    carved = _coalesce.moved(sources, _coalesce.CARVED)
    if frames is None or carved is None or frames <= 0:
        return None
    return 100.0 * carved / frames
