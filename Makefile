# Dev loop for ratelimiter_tpu (reference Makefile:16-93 analog).
# All targets run against the repo in place (the package is not installed).

PY ?= python
REPO := $(abspath $(dir $(lastword $(MAKEFILE_LIST))))
export PYTHONPATH := $(REPO):$(PYTHONPATH)

.PHONY: help test test-all test-serving test-mesh test-collective test-tracing test-chaos \
        test-audit test-fleet test-fleet-forward test-fleet-obs \
        test-reshard test-hierarchy test-leases test-placement test-shm \
        test-neteng lint check \
        native serve verify smoke clean

help:            ## list targets
	@grep -E '^[a-z-]+:.*##' $(MAKEFILE_LIST) | sed 's/:.*##/\t/'

test:            ## fast suite (CPU, 8 virtual devices; excludes slow gates)
	$(PY) -m pytest tests/ -q -m "not slow"

test-all:        ## full suite including slow accuracy/scale gates
	$(PY) -m pytest tests/ -q

test-serving:    ## serving tier only
	$(PY) -m pytest tests/test_serving.py -q

test-mesh:       ## mesh contract + multichip + slice-parallel serving tests
	$(PY) -m pytest tests/test_contract_mesh.py tests/test_multichip.py \
	    tests/test_mesh_serving.py tests/test_scatter_gather.py -q

test-collective: ## collective router parity + overflow fallback (ADR-024)
	$(PY) -m pytest tests/test_collective_router.py tests/test_collective_deployment.py -q

test-tracing:    ## flight-recorder span trees, both doors (ADR-014)
	$(PY) -m pytest tests/test_tracing.py -q

test-chaos:      ## failure-domain chaos suite + client resilience (ADR-015)
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PY) -m pytest tests/test_chaos.py tests/test_client_resilience.py -q

test-audit:      ## live accuracy observatory (ADR-016): engine, taps, /debug/audit
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PY) -m pytest tests/test_audit.py -q

test-fleet:      ## fleet tier (ADR-017): map/routing/forwarding/failover, 2+ real server processes
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet.py -q

test-fleet-forward: ## coalesced forward lanes (ADR-019): ordering oracle, window failure attribution, 4-host routing
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet_forward.py -q

test-fleet-obs:  ## fleet control tower (ADR-021): trace stitching, mergeable rollup, event journal, metric-name drift gate (slow lane unfiltered)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet_obs.py \
	    tests/test_metrics_docs.py -q

test-reshard:    ## elastic lifecycle (ADR-018): re-bucketing oracle, migration/rejoin/departure, handoff chaos
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PY) -m pytest tests/test_reshard.py tests/test_elastic.py -q

test-hierarchy:  ## hierarchical cascades + AIMD (ADR-020): oracle pinning, fair share, controller, both doors, mesh
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PY) -m pytest tests/test_hierarchy.py tests/test_hierarchy_serving.py -q

test-leases:     ## client-embedded quota leases (ADR-022): protocol, debit-upfront oracle, revocation chaos, kill -9, both doors, fleet
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_leases.py -q

test-placement:  ## load-aware placement (ADR-023): planner determinism, chaos rebalance oracle, journal spill, real-process operator flow (slow lane unfiltered)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_placement.py -q

test-shm:        ## shared-memory wire lane (ADR-025): uds/shm both doors, bit-identical pins, kill -9, ring fuzz, revocation-over-shm
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_shm_transport.py -q

test-neteng:     ## multi-ring network engine (ADR-026): epoll==uring byte parity, asserted probe downgrade, mid-frame death, slow-loris, fairness, shm-over-uring
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_net_engine.py -q

lint:            ## in-repo linter (ruff config in pyproject.toml where available)
	$(PY) tools/lint.py

check: lint test ## what CI runs on every push

cpp-client:      ## build + conformance-test the native C++ client
	$(PY) -m pytest tests/test_cpp_client.py -q

native:          ## build the C++ bulk hasher extension in place (rebuilds when hasher.cpp changed)
	$(PY) -c "from ratelimiter_tpu.native import native_available; \
	          assert native_available(), 'build failed (g++ required)'; \
	          print('native hasher built')"

serve:           ## run the server binary locally (exact backend, instant start)
	$(PY) -m ratelimiter_tpu.serving --backend exact --algorithm fixed_window \
	    --limit 100 --window 60 --port 8432

smoke:           ## chip_smoke.py as a CPU rehearsal: all four legs at tiny geometry on 4 virtual devices; says cpu, prints no result, exits 3. On a chip: python chip_smoke.py
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PY) chip_smoke.py; test $$? -eq 3

verify:          ## driver protocol: entry() compile + 8-device mesh dry run
	XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
	    $(PY) __graft_entry__.py

clean:           ## remove caches and build artifacts
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
	rm -f ratelimiter_tpu/native/_hasher.so ratelimiter_tpu/native/_server.so
	rm -rf .pytest_cache
