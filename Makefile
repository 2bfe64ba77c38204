# consensus_specs_tpu — developer entry points (the reference's
# Makefile:73-271 equivalents, adapted: no pip installs are available in
# this environment, so `lint` is a compile + full-spec-build check instead
# of ruff/mypy).

PYTHON ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
VECTOR_OUT ?= out/vectors

.PHONY: test test-fast test-all test-bls lint vectors kzg_setups bench \
	bench-smoke bench-report serve serve-smoke chaos-smoke \
	chaos-mesh-smoke shard-smoke das-smoke fc-smoke multichip \
	incident help

help:
	@echo "targets: test (fast suite) | test-all (incl. slow crypto) |"
	@echo "  test-bls (operation suites with real signatures, jax backend) |"
	@echo "  lint (compile + spec static checks + device-path analyzer) |"
	@echo "  vectors [VECTOR_OUT=dir] |"
	@echo "  kzg_setups | bench (real TPU) | bench-smoke (tiny CPU shapes,"
	@echo "  asserts the bench JSON contract) | bench-report (benchwatch"
	@echo "  trend/threshold dashboard over the round files in --repo +"
	@echo "  out/bench_history.jsonl; exits nonzero on regression) |"
	@echo "  serve (sustained-load verification service, real TPU;"
	@echo "  CST_TRACE_REQUESTS=1 adds per-request tail-latency"
	@echo "  attribution, CST_SERVE_STATUS_EVERY=N live status dumps) |"
	@echo "  serve-smoke (short closed-loop CPU serve round with request"
	@echo "  tracing, emits the serve bench JSON + benchwatch history +"
	@echo "  worst-N exemplar traces) | chaos-smoke (serve"
	@echo "  round under a canned fault plan: breaker/oracle-fallback"
	@echo "  degraded mode, checkpoint kill/restore, flagship breaker,"
	@echo "  recovery-to-steady, resilience records) | chaos-mesh-smoke"
	@echo "  (same + shard-loss recovery on a simulated 8-device mesh) |"
	@echo "  shard-smoke (tiny mesh-sharded flagship scaling rung on the"
	@echo "  simulated 8-device mesh, asserts the scaling::* record"
	@echo "  round-trip + report) | das-smoke (PeerDAS cell-proof sweep"
	@echo "  at the 128x8 sampling matrix on CPU: das block schema,"
	@echo "  >=2x speedup vs the pure-Python oracle, FK20 producer +"
	@echo "  recover round, das::* round-trip + report) | fc-smoke"
	@echo "  (device LMD-GHOST sweep on a tiny CPU"
	@echo "  tree: forkchoice block schema, >=2x speedup vs the phase0"
	@echo "  spec oracle, bit-exact head parity, forkchoice::*"
	@echo "  round-trip + report) | incident (on-demand flight-recorder"
	@echo "  bundle -> out/incidents/) | multichip (8-dev CPU dryrun)"

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
	$(PYTHON) -m compileall -q consensus_specs_tpu tests bench.py __graft_entry__.py
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

bench:
	$(PYTHON) bench.py

# no TPU required: tiny-shape epoch + BLS bench runs on CPU, asserting
# the one-JSON-line-per-metric contract the external driver parses —
# including the CST_TELEMETRY "telemetry" sub-object (compile/run split,
# padding waste, MSM/h2c routing) and the CST_TRACE_FILE Chrome trace
bench-smoke:
	$(CPU_ENV) $(PYTHON) bench_smoke.py

# benchwatch: ingest the driver's BENCH_r*/MULTICHIP_r* round files found
# in the checkout (only MULTICHIP_r04/r05 are checked in), the oracle
# baselines, and any
# telemetry snapshot into out/bench_history.jsonl, render the markdown
# trend + ROADMAP-threshold dashboard (out/bench_report.md), and exit
# nonzero on a round-over-round regression (CI gates on this; stdlib
# only, no jax)
bench-report:
	$(PYTHON) -m consensus_specs_tpu.telemetry.report --out out/bench_report.md

# the sustained-load attestation-verification service benchmark
# (consensus_specs_tpu/serve): mainnet-rate arrival mix through the
# deferred-futures executor, reports steady-state verifies/sec +
# p50/p99 batch latency (CST_SERVE_* knobs, README "Serving")
serve:
	$(PYTHON) bench_serve.py

# no TPU required: short closed-loop serve round on tiny CPU shapes —
# the measured rate is the host's capacity, the JSON contract, the
# serve::* history records, and (CST_TRACE_REQUESTS=1) the per-request
# latency_attribution block + worst-N exemplar artifact are what CI
# checks.  CST_METRICS_PORT + CST_SLO_RULES arm the live exposition
# endpoint (self-scraped mid-round into out/metrics_scrape.txt) and
# the SLO watchdog (evidence -> out/slo_breaches.json, slo::* records
# for the slo-clean-round report row); the generous thresholds mean a
# healthy round ends clean — breaches here are real findings
serve-smoke:
	@$(CPU_ENV) CST_SERVE_DURATION_S=12 CST_SERVE_RATE=0 CST_SERVE_POOL=4 \
		CST_SERVE_COMMITTEE=4 CST_SERVE_MAX_BATCH=8 CST_SERVE_WINDOWS=3 \
		CST_TRACE_REQUESTS=1 CST_METRICS_PORT=9464 CST_OCCUPANCY=1 \
		CST_SLO_RULES='serve.p99_ms<100000:name=p99-sane; serve.queue_depth<100000:name=queue-sane' \
		$(PYTHON) bench_serve.py

# no TPU required: the chaos round — bench_serve under CST_SERVE_CHAOS=1
# with a canned fault plan injecting dispatch failures into the RLC
# kernel.  Asserts zero wrong results, breaker trip -> oracle-fallback
# degraded mode -> re-close, finite recovery latency, the "resilience"
# block schema, the resilience::* history round-trip, and the report's
# Resilience section + chaos-recovery threshold row (CI gates on this)
chaos-smoke:
	$(CPU_ENV) $(PYTHON) bench_smoke.py --chaos

# on-demand incident dump from whatever process state is reachable:
# writes a self-contained bundle (manifest + event ring + fault plan +
# exemplars + metrics + state) under out/incidents/ and validates its
# own manifest.  The automatic triggers are CST_FLIGHTREC_ON_BREACH=1
# (one bundle per breached SLO rule) and CST_FLIGHTREC_POISON_N (poison
# storms) — see README "Flight recorder"
incident:
	$(CPU_ENV) $(PYTHON) -m consensus_specs_tpu.telemetry.flightrec

# no TPU required: the simulated-mesh chaos round — CPU_ENV forces 8
# host devices, CST_CHAOS_MESH arms the shard-loss segment: one
# injected device_loss into batch_verify_sharded, the lost shard's
# statements re-bucket over the surviving 7 devices (zero wrong or
# dropped), an invalid statement still rejects while degraded, and the
# half-open probe re-admits the full mesh.  Asserts the mesh::* record
# round-trip + the mesh-recovery / mesh-lost-statements threshold rows
chaos-mesh-smoke:
	$(CPU_ENV) $(PYTHON) bench_smoke.py --chaos-mesh

# no TPU required: a tiny mesh-sharded flagship scaling rung on the
# simulated 8-device mesh (the partition-registry epoch pipeline),
# asserting the "scaling" block schema, the scaling::* history-record
# round-trip, and the report's Scaling section.  The TPU-gated
# scaling-efficiency / flagship-8m threshold rows read 'no data' here —
# the smoke pins the plumbing, the chip pins the number
shard-smoke:
	$(CPU_ENV) $(PYTHON) bench_smoke.py --shard

# no TPU required: the PeerDAS cell-proof sweep at the full 128x8
# sampling matrix (1024 cells in ONE RLC pairing equation — the
# largest device batch in the repo).  Asserts the "das" block schema,
# the >= 2x das-speedup acceptance vs the pure-Python fulu oracle
# (oracle measured on a cell subset and scaled — its per-cell Lagrange
# interpolation makes a full-matrix oracle run hours), the
# mixed-invalid isolation arc, the coset-barycentric cross-check, and
# the das::* history/report/threshold wiring (CI gates on this).
# The same run covers the FK20 producer + damaged-matrix recover
# round: byte-parity vs the closed form, >= 4x das-producer-speedup
# vs the D_u MSM route, >= 2x das-recover-speedup vs the pure-Python
# recover oracle (both CPU-evaluable)
das-smoke:
	$(CPU_ENV) $(PYTHON) bench_smoke.py --das

# no TPU required: the device LMD-GHOST sweep on a tiny CPU tree (64
# blocks x 1024 validators).  Asserts the "forkchoice" block schema,
# the >= 2x fc-speedup acceptance vs the phase0 spec oracle's
# get_head (the oracle walks every active validator per child in pure
# Python; measured on a validator subset and scaled linearly),
# bit-exact device-vs-oracle head parity, and the forkchoice::*
# history/report/threshold wiring (CI gates on this)
fc-smoke:
	$(CPU_ENV) $(PYTHON) bench_smoke.py --forkchoice

multichip:
	$(CPU_ENV) $(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"
