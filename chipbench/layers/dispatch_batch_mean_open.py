from chipbench.layers import dispatch_batch_mean as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
