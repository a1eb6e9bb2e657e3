from chipbench.layers import device_idle_pct as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
