"""The dispatcher thread blocked with nothing it may drain — the empty
queue and the ``--max-delay-us`` coalescing wait — per dispatch:
``rate_limiter_door_thread_seconds_total{thread="dispatcher",
state="idle"}`` over ``rate_limiter_door_dispatches_total``
(chipbench/layers/_threads.py). ~0 under a closed loop means the
dispatcher paces the cell: it never waits for work. None before PR 37."""

from chipbench.layers import _threads, closed_loop

META = {"name": "dispatcher_idle_us_per_dispatch", "unit": "us",
        "better": "lower", "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_counter", "applies": closed_loop}


def read(sources: dict):
    return _threads.state_us(sources, "dispatcher", "idle")
