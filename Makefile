PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-sharded bench-smoke bench bench-latency bench-prefill bench-prefix bench-spec bench-elastic bench-fused bench-obs bench-shard bench-adapters serve-demo serve-adapters-demo

# tier-1 verify (ROADMAP.md)
test:
	$(PYTHON) -m pytest -x -q

# tensor-parallel parity matrix on 8 forced host devices (the env var must
# be set before the first jax import, so it lives on the pytest invocation,
# not inside the test module)
test-sharded:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(PYTHON) -m pytest tests/test_sharded_serving.py -q

# quick serving-throughput benchmark: the per-slot reference engine vs the
# paged engine (interpret-mode kernels on CPU)
bench-smoke:
	$(PYTHON) -m benchmarks.serve_throughput --quick

# latency SLO harness: the paged engine under Poisson arrivals
bench-latency:
	$(PYTHON) -m benchmarks.serve_latency --quick

# chunked prefill: mixed long/short-prompt workload, one-shot vs chunked
# prefill on the paged engine (short-request tail ITL is the headline)
bench-prefill:
	$(PYTHON) -m benchmarks.serve_latency --mixed --quick

# prefix sharing: radix prompt cache on vs off at equal KV budget
# (shared-prefix burst TTFT, multi-turn hit rate, eviction-resume reattach)
bench-prefix:
	$(PYTHON) -m benchmarks.serve_prefix --quick

# speculative decode: elastic low-budget draft vs the paged engine
bench-spec:
	$(PYTHON) -m benchmarks.serve_spec --quick

# elastic tiers: per-tier tok/s, tier-switch latency (no re-jit on switch),
# admitted rate under page pressure with the tier controller on vs off
bench-elastic:
	$(PYTHON) -m benchmarks.serve_elastic --quick

# fused SLR kernel: one-pass low-rank+sparse vs separate calls — engine
# tok/s, jitted decode-step latency, per-kernel HBM bytes + roofline
bench-fused:
	$(PYTHON) -m benchmarks.kernel_bench --quick

# telemetry overhead: metrics + tracing on vs off on one engine — streams
# must be bitwise identical, tok/s overhead target < 2% (BENCH_obs.json).
# Runs the FULL 60m model (not --quick): the overhead must be weighed
# against realistic per-tick device work for the percentage to mean much
bench-obs:
	$(PYTHON) -m benchmarks.serve_obs

# tensor-parallel serving: tok/s + per-device KV pool bytes at mesh 1/2/4
# under an equal total KV budget (the script forces 8 host devices itself)
bench-shard:
	$(PYTHON) -m benchmarks.serve_shard --quick

# multi-tenant adapters: one AdapterBank engine (batched heterogeneous-
# adapter kernel) vs a per-tenant engine fleet at equal aggregate KV budget
bench-adapters:
	$(PYTHON) -m benchmarks.serve_adapters --quick

# full scaled-down paper benchmark suite
bench:
	$(PYTHON) -m benchmarks.run --quick

# elastic-deployment spectrum: ONE engine serving all three budget tiers
serve-demo:
	$(PYTHON) -m repro.launch.serve --arch salaad_llama_60m --reduced \
	    --keep-ratios 1.0,0.6,0.3 --fmt factored --requests 8 \
	    --tier-policy pressure

# multi-tenant spectrum: ONE engine serving 8 registered adapters over a
# shared base, with a 4-row device pool exercising LRU swaps
serve-adapters-demo:
	$(PYTHON) -m repro.launch.serve --arch salaad_llama_60m --reduced \
	    --fmt fused --adapters 8 --max-resident-adapters 4 --requests 16
