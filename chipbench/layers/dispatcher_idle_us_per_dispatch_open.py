from chipbench.layers import dispatcher_idle_us_per_dispatch as closed, twin

META = twin(closed, "latency_p50_ms")
read = closed.read
