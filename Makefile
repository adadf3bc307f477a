# Build/test entry points (reference: Makefile:21-140).

PYTHON ?= python
IMAGE_REGISTRY ?= ghcr.io/example
IMAGE_TAG ?= latest
CELL ?= mistral-7b.chat

.PHONY: test test-fast native bench lint images dryrun chip-smoke clean

# what the driver runs (its command is cut at 1,470 s; ROADMAP "Tier-1"):
# the same selection on the same six workers, a file to a worker, so that a
# builder sees the seconds the driver will, and the slowest tests by name
test:
	JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 $(PYTHON) -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly --durations=15

test-fast:
	$(PYTHON) -m pytest tests/ -q -x

native:
	$(MAKE) -C native

# the benchmark (BENCHMARK.json): one untraced run of one cell, on the chip
# (run through the chip tool; fails without a TPU). The record is
# PERF_LEDGER.jsonl and PERF.md
bench:
	$(PYTHON) -m fmabench --workload $(CELL) --seed 1 --seconds 50 --trace 0

# the control plane's T_actuation harness, simulated (no cluster, no TPU)
bench-actuation:
	$(PYTHON) -m llm_d_fast_model_actuation_tpu.benchmark --scenario all

# eight virtual CPU devices: asked for, never fallen back to
dryrun:
	JAX_PLATFORMS=cpu $(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# the quickest proof the system still starts on the chip (run through the
# chip tool; fails without a TPU)
chip-smoke:
	$(PYTHON) chip_smoke.py

images:
	docker build -f deploy/dockerfiles/Dockerfile.launcher -t $(IMAGE_REGISTRY)/fma-tpu-launcher:$(IMAGE_TAG) .
	docker build -f deploy/dockerfiles/Dockerfile.requester -t $(IMAGE_REGISTRY)/fma-tpu-requester:$(IMAGE_TAG) .
	docker build -f deploy/dockerfiles/Dockerfile.controller -t $(IMAGE_REGISTRY)/fma-tpu-controller:$(IMAGE_TAG) .

clean:
	rm -rf native/build .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
