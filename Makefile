# Developer entry points (reference analogue: Makefile:191-359)

PYTHON ?= python

.PHONY: help install test test-fast lint speclint jaxlint rangelint reftests bytediff multichip recovery-smoke postmortem serve_docs coverage clean

help:
	@echo "install    - editable install with test extras"
	@echo "test       - FAST lane: suite minus @slow (CPU, 8 virtual devices)"
	@echo "test-full  - everything incl. @slow (the nightly lane)"
	@echo "test-slow  - only the @slow modules"
	@echo "lint       - ruff check (if installed) + speclint + jaxlint + rangelint + env-docs diff"
	@echo "speclint   - AST-level project-native static analysis (docs/analysis.md)"
	@echo "jaxlint    - trace-level kernel analysis: transfers, donation,"
	@echo "             recompile surfaces, mesh collectives (docs/analysis.md)"
	@echo "rangelint  - value-range kernel analysis: interval proof that no"
	@echo "             limb intermediate wraps a lane (docs/analysis.md)"
	@echo "reftests   - emit test vectors to ./test_vectors"
	@echo "bytediff   - conformance byte-diff vs the compiled reference spec"
	@echo "multichip  - 8-virtual-device sharding dry run"
	@echo "postmortem - pretty-print the most recent flight-recorder bundle"
	@echo "clean      - remove caches and generated vectors"

install:
	$(PYTHON) -m pip install -e .[test]

# The default lane mirrors the reference's split: `make test` is the
# developer loop (reference Makefile:227-249), the heavy device-compile /
# pure-python-crypto / mainnet differential modules run nightly
# (reference .github/workflows/nightly-tests.yml).
test:
	$(PYTHON) -m pytest tests/ -q -m "not slow" -p xdist -n auto

test-full:
	$(PYTHON) -m pytest tests/ -q -p xdist -n auto

test-slow:
	$(PYTHON) -m pytest tests/ -q -m slow -p xdist -n auto

test-serial:
	$(PYTHON) -m pytest tests/ -q -m "not slow"

parity:
	$(PYTHON) -m pytest tests/parity/ -q -m "not slow"

parity-full:
	$(PYTHON) -m pytest tests/parity/ -q

# mainnet-SHAPED smoke: full 16,384-validator genesis, 64-committee slots,
# mainnet preset — a driver-runnable subset (not nightly-only).  The
# attestation-dense suites stay in `make test` under SPEC_TEST_PRESET.
mainnet-smoke:
	SPEC_TEST_PRESET=mainnet $(PYTHON) -m pytest \
	  tests/phase0/test_sanity.py tests/phase0/test_process_attestation.py \
	  tests/phase0/test_block_operations.py \
	  -k "empty_block or slots_1 or invalid_state_root or one_basic or proposer_slashing_basic or deposit_top_up" \
	  -q

test-fast: test

# ruff (style, best-effort) then speclint (AST-level project invariants,
# GATING: fork-safety, lock-order, jit-purity, obs/env/fault registries)
# then jaxlint (trace-level kernel invariants, GATING: transfer-free,
# donation-audit, recompile-surface, collective-audit, constant-bloat,
# x64-drift — docs/analysis.md) then rangelint (value-range invariants,
# GATING: lane-overflow, mask-consistency, lazy-bound-audit);
# env-reference.md must match the registry
lint:
	-$(PYTHON) -m ruff check eth_consensus_specs_tpu/ tests/
	$(PYTHON) scripts/speclint.py
	JAX_PLATFORMS=cpu $(PYTHON) scripts/jaxlint.py
	JAX_PLATFORMS=cpu $(PYTHON) scripts/rangelint.py
	$(PYTHON) scripts/gen_env_docs.py --check

speclint:
	$(PYTHON) scripts/speclint.py

# trace-level analysis of every registered kernel (analysis/kernels.py);
# --chips 8 is the CLI default, so the three mesh-sharded variants are
# analyzed on 8 virtual CPU devices even on a 1-device dev box
jaxlint:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/jaxlint.py

# value-range analysis: interval abstract interpretation over every
# registered kernel's jaxpr, seeded from the registry's declared input
# domains — proves no intermediate can wrap a u64/u32 lane
rangelint:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/rangelint.py

reftests:
	$(PYTHON) -m eth_consensus_specs_tpu.gen -o test_vectors -v

# cross-generator conformance byte-diff (docs/conformance-bytediff.md):
# emit the agreed slice, replay every vector through the specc-compiled
# reference markdown, require byte-identical post-states.  The script's
# exit code IS the gate — no pipeline may mask it.
bytediff:
	$(PYTHON) scripts/cross_gen_bytediff.py > BYTEDIFF_RESULT.json; \
	s=$$?; cat BYTEDIFF_RESULT.json; exit $$s

multichip:
	$(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"

# durable-resident-state chaos gate: SIGKILL the resident replica at
# the checkpoint commit seam, restore-then-replay, bit-identical root
# vs an uninterrupted control run (docs/robustness.md)
recovery-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/recovery_smoke.py --out recovery_smoke.json

# most recent flight-recorder bundle ($ETH_SPECS_OBS_POSTMORTEM_DIR or
# ./postmortems); `scripts/postmortem.py --list` / `A B` to diff
postmortem:
	$(PYTHON) scripts/postmortem.py

serve_docs:
	$(PYTHON) -m mkdocs serve

coverage:
	$(PYTHON) scripts/spec_coverage.py

clean:
	rm -rf .pytest_cache .jax_cache test_vectors
	find . -name __pycache__ -type d -exec rm -rf {} +
