from chipbench.layers import programs_per_dispatch as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
