from chipbench.layers import dispatch_us_exact as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
