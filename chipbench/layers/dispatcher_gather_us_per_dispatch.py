"""The dispatcher from its drain stamp (``t_d0``) to its call of
``PyGILState_Ensure``, per dispatch: the C++ column gather
(``build_buffers`` / ``build_hashed_buffers``) — the first half of the
ring's ``enter`` stage. ``…thread_seconds_total{thread="dispatcher",
state="gather"}`` over dispatches (_threads.py). None before PR 37."""

from chipbench.layers import _threads, closed_loop

META = {"name": "dispatcher_gather_us_per_dispatch", "unit": "us",
        "better": "lower", "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_counter", "applies": closed_loop}


def read(sources: dict):
    return _threads.state_us(sources, "dispatcher", "gather")
