"""The completer inside ``PyGILState_Ensure``, per dispatch: its wait for
the interpreter before the resolve callback. The door stamps ``t_v0``
before that call, so this wait is inside the ``device`` stage
(``resolve_wait_us_per_dispatch``). ``…thread_seconds_total{thread=
"completer",state="gil"}`` over dispatches (_threads.py). None before
PR 37."""

from chipbench.layers import _threads, closed_loop

META = {"name": "completer_gil_wait_us_per_dispatch", "unit": "us",
        "better": "lower", "layer": "resolve / pack",
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": closed_loop}


def read(sources: dict):
    return _threads.state_us(sources, "completer", "gil")
