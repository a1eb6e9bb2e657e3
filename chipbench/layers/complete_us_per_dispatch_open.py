from chipbench.layers import complete_us_per_dispatch as closed, twin

META = twin(closed, "latency_p99_ms")
read = closed.read
