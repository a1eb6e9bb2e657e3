"""The dispatcher inside ``cv_space.wait`` — every ``--inflight`` slot
taken — per dispatch: what of the ring's ``leave`` stage is
back-pressure from the device or the resolve side (the rest of ``leave``
is the GIL's release). ``…thread_seconds_total{thread="dispatcher",
state="slot"}`` over dispatches (_threads.py). 0 where a slot is always
free; None before PR 37."""

from chipbench.layers import _threads, closed_loop

META = {"name": "dispatcher_slot_wait_us_per_dispatch", "unit": "us",
        "better": "lower", "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_counter", "applies": closed_loop}


def read(sources: dict):
    return _threads.state_us(sources, "dispatcher", "slot")
