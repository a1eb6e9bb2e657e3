"""Resolves whose reply columns the ONE native pass built, per dispatch of
the door: the difference of ``rate_limiter_result_native_unpacks_total``
(counted by the program beside its fetches, every shard label summed) over
the difference of ``rate_limiter_door_dispatches_total``. 1.0 where the
server's host built the extension (``native/hasher.cpp:unpack_columns``);
0.0 where it serves from the NumPy twins — the same answers, 4 to 14
array calls a resolve that each let go of the interpreter. The ledger's
only way to say which of the two a measured machine ran. A count; None
where the program exports no such counter (before PR 52) or the door
completed nothing between the scrapes."""

from chipbench import promtext
from chipbench.layers import closed_loop

META = {"name": "native_unpacks_per_dispatch", "unit": "1/dispatch",
        "better": "higher", "layer": "resolve / pack",
        "moves": "decisions_per_s", "source": "program_counter",
        "applies": closed_loop}

UNPACKS = "rate_limiter_result_native_unpacks_total"


def read(sources: dict):
    a, b = sources["metrics_start"], sources["metrics_end"]
    if not any(name == UNPACKS for name, _ in b):
        return None
    dispatches = promtext.delta(a, b, "rate_limiter_door_dispatches_total")
    if dispatches <= 0:
        return None
    return promtext.delta(a, b, UNPACKS) / dispatches
