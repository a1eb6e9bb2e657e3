from chipbench.layers import dispatch_covered_pct as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
