from chipbench.layers import completer_gil_wait_us_per_dispatch as closed, \
    twin

META = twin(closed, "latency_p50_ms")
read = closed.read
