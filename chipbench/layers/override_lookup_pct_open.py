from chipbench.layers import override_lookup_pct as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
