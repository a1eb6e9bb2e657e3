"""The share of its wall the dispatcher thread computes: 100 x
``rate_limiter_door_thread_cpu_seconds_total{thread="dispatcher"}`` over
the sum of its ``…thread_seconds_total`` states, both between the
scrapes (_threads.py). Low with ``idle`` ~0: the thread is blocked, not
busy — inside the interpreter (the GIL handed back late, a lock) or in
a transfer. None before PR 37."""

from chipbench.layers import _threads, closed_loop

META = {"name": "dispatcher_cpu_pct", "unit": "%", "better": "higher",
        "layer": "dispatch", "moves": "decisions_per_s",
        "source": "program_counter", "applies": closed_loop}


def read(sources: dict):
    return _threads.cpu_pct(sources, "dispatcher")
