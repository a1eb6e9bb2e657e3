"""The dispatcher inside ``PyGILState_Ensure``, per dispatch: its wait
for the interpreter before the launch callback — the second half of the
ring's ``enter`` stage, and the answer to "who holds the GIL" from the
dispatcher's side. ``…thread_seconds_total{thread="dispatcher",
state="gil"}`` over dispatches (_threads.py). None before PR 37."""

from chipbench.layers import _threads, closed_loop

META = {"name": "dispatcher_gil_wait_us_per_dispatch", "unit": "us",
        "better": "lower", "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_counter", "applies": closed_loop}


def read(sources: dict):
    return _threads.state_us(sources, "dispatcher", "gil")
