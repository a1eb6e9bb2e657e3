from chipbench.layers import fetches_per_dispatch as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
