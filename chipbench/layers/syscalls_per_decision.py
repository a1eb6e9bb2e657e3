"""Network syscalls the door made per decision it answered (a count)."""

from chipbench import promtext
from chipbench.layers import open_loop

META = {"name": "syscalls_per_decision", "unit": "1/decision",
        "better": "lower", "layer": "wire + door", "moves": "latency_p50_ms",
        "source": "program_counter", "applies": open_loop}


def read(sources: dict):
    a, b = sources["metrics_start"], sources["metrics_end"]
    decisions = promtext.delta(a, b, "rate_limiter_server_batch_size_sum")
    if decisions <= 0:
        return None
    return promtext.delta(a, b, "rate_limiter_net_syscalls_total") / decisions
