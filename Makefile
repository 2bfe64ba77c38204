# consensus_specs_tpu — developer entry points (the reference's
# Makefile:73-271 equivalents, adapted: no pip installs are available in
# this environment, so `lint` is a compile + full-spec-build check instead
# of ruff/mypy).

PYTHON ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
VECTOR_OUT ?= out/vectors

.PHONY: test test-fast test-all test-bls lint vectors kzg_setups \
	multichip incident help

help:
	@echo "targets: test (fast suite) | test-all (incl. slow crypto) |"
	@echo "  test-bls (operation suites with real signatures, jax backend) |"
	@echo "  lint (compile + spec static checks + device-path analyzer) |"
	@echo "  vectors [VECTOR_OUT=dir] | kzg_setups |"
	@echo "  incident (on-demand flight-recorder bundle -> out/incidents/) |"
	@echo "  multichip (8-dev CPU dryrun).  The benchmark is"
	@echo "  'python3 benchmark/run.py --workload <cell>' (BENCHMARK.json)"

test:
	$(PYTHON) -m pytest tests/ -q -m "not slow"

# the reference's default is BLS ON (`Makefile:105` bls=fastest); this
# lane runs the signature-sensitive suites with real crypto on the jax
# backend so invalid-signature rejection paths execute every round
test-bls:
	$(CPU_ENV) $(PYTHON) -m pytest \
		tests/phase0/block_processing tests/electra/block_processing \
		tests/eip7732 tests/test_executor.py \
		-q --enable-bls --bls-type=jax

test-all:
	$(PYTHON) -m pytest tests/ -q

lint:
	$(PYTHON) -m compileall -q consensus_specs_tpu tests chip_smoke.py __graft_entry__.py
	$(CPU_ENV) $(PYTHON) -m consensus_specs_tpu.lint
	$(PYTHON) -m consensus_specs_tpu.analysis

vectors:
	$(CPU_ENV) $(PYTHON) -m consensus_specs_tpu.gen --output $(VECTOR_OUT) \
		--runners sanity operations epoch_processing finality genesis \
		rewards random transition forks shuffling ssz_generic networking

kzg_setups:
	$(CPU_ENV) $(PYTHON) -m consensus_specs_tpu.utils.kzg_setup \
		--secret 1337 --g1-length 4096 --g2-length 65 \
		--output-dir out/trusted_setups

# on-demand incident dump from whatever process state is reachable:
# writes a self-contained bundle (manifest + event ring + fault plan +
# exemplars + metrics + state) under out/incidents/ and validates its
# own manifest.  The automatic triggers are CST_FLIGHTREC_ON_BREACH=1
# (one bundle per breached SLO rule) and CST_FLIGHTREC_POISON_N (poison
# storms) — see README "Flight recorder"
incident:
	$(CPU_ENV) $(PYTHON) -m consensus_specs_tpu.telemetry.flightrec

multichip:
	$(CPU_ENV) $(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"
