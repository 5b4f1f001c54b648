# CI entry points — `make verify` is the PR gate (lint + tier-1 tests).
#
#   make lint         kschedlint AST rules + Level-3 program-coverage
#                     sweep over the library and tools (every
#                     jit/pallas_call/shard_map site registered or
#                     waived; prints the L3 summary line)
#   make test         tier-1 pytest as the driver runs it: CPU, 8-dev
#                     mesh, xdist `-n 6 --dist loadfile`, 1,470 s limit;
#                     the count of passes is read from the junit file
#   make chaos-smoke  short fixed-seed chaos soak (fault injection +
#                     degradation ladder + restore + determinism check;
#                     docs/robustness.md)
#   make obs-smoke    short chaos soak serving live /metricsz; scrapes
#                     its own endpoint and asserts the served counters
#                     reconcile exactly with the RoundRecord totals,
#                     AND that the seeded solver faults produced a
#                     flight dump carrying the stall detector's
#                     structured reason + telemetry tail
#                     (docs/observability.md)
#   make pipeline-smoke  short double-buffered chaos soak asserting
#                     bit-identical placements across the sync,
#                     pipelined, and pipelined+device-resident service
#                     loops, including mid-flight rung degradation
#                     (docs/round_pipeline.md)
#   make tenant-smoke 16-cell multi-tenant soak: one warm batched-solver
#                     process, mixed cell sizes, chaos injected into ONE
#                     tenant — asserts per-tenant placements bit-identical
#                     to each tenant run in isolation, zero cross-tenant
#                     interference in the round trace, and reports
#                     per-tenant p50/p99 (docs/multitenancy.md)
#   make recovery-smoke  state-integrity soak: seeded device-buffer
#                     corruption + two mid-soak kill-and-restores vs a
#                     clean control run — asserts 100% corruption
#                     detection (fingerprint audits), zero false
#                     positives, warm delta-sized restores, and
#                     bit-identical placements (docs/robustness.md)
#   make shard-smoke  multi-chip rung churn soak on the virtual
#                     8-device CPU mesh: sharded placements
#                     bit-identical to the single-chip scan-CSR arm
#                     over the same layout, delta-sized sharded plan
#                     syncs after warm-up (zero layout rebuilds, zero
#                     build_sharded_plan argsorts), chaos containment
#                     via the sharded -> jax -> cpu_ref ladder
#                     (docs/sharding.md)
#   make chip-smoke-rehearse  chip_smoke.py --rehearse-cpu: every phase
#                     of the on-chip smoke at tiny sizes on the CPU (4
#                     virtual devices so the sharded phase runs too),
#                     Pallas under the interpreter by explicit mode.
#                     The real thing is `python chip_smoke.py` on a
#                     machine with a TPU; it refuses to start without one
#   make verify       lint, then tests, then the smokes
#   make baseline     re-accept current lint violations (ratchet; avoid —
#                     fix or suppress inline instead, docs/static_analysis.md)

SHELL := /bin/bash

PY ?= python
LINT_PATHS = ksched_tpu tools

.PHONY: lint test chaos-smoke obs-smoke pipeline-smoke tenant-smoke recovery-smoke shard-smoke chip-smoke-rehearse verify baseline

lint:
	$(PY) -m tools.kschedlint --coverage $(LINT_PATHS)

chaos-smoke:
	timeout -k 10 300 env JAX_PLATFORMS=cpu $(PY) tools/soak.py --chaos \
	  --rounds 96 --chunk 32 --seed 0 --machines 6 --slots 8 \
	  --chaos-restore-every 48 --verify-determinism

obs-smoke:
	rm -rf /tmp/ksched_obs_smoke_flight
	timeout -k 10 300 env JAX_PLATFORMS=cpu $(PY) tools/soak.py --chaos \
	  --rounds 64 --chunk 32 --seed 3 --machines 6 --slots 8 \
	  --chaos-restore-every 0 --metrics-port 0 \
	  --flight-dir /tmp/ksched_obs_smoke_flight --solver-outage-prob 0.08 \
	  --assert-stall-flight

pipeline-smoke:
	timeout -k 10 300 env JAX_PLATFORMS=cpu $(PY) tools/soak.py --chaos \
	  --rounds 64 --chunk 32 --seed 5 --machines 6 --slots 8 \
	  --chaos-restore-every 32 --verify-loop-parity

tenant-smoke:
	timeout -k 10 570 env JAX_PLATFORMS=cpu $(PY) tools/soak.py \
	  --tenants 16 --rounds 40 --seed 0 --chaos-tenant 0

recovery-smoke:
	timeout -k 10 300 env JAX_PLATFORMS=cpu $(PY) tools/soak.py --chaos \
	  --rounds 512 --chunk 128 --seed 11 --machines 6 --slots 8 \
	  --chaos-restore-every 128 --verify-recovery

shard-smoke:
	timeout -k 10 300 env JAX_PLATFORMS=cpu $(PY) tools/shard_smoke.py \
	  --machines 6 --tasks 48 --rounds 24 --warmup 4 --devices 8 --seed 7

chip-smoke-rehearse:
	timeout -k 10 300 env XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	  $(PY) chip_smoke.py --rehearse-cpu

test:
	set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; \
	timeout -k 10 1470 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml \
	  -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	said=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null \
	  | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); \
	echo DOTS_PASSED=$${said:-$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)}; \
	echo WORKERS_DOWN=$$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); \
	exit $$rc

verify: lint test chaos-smoke obs-smoke pipeline-smoke tenant-smoke recovery-smoke shard-smoke chip-smoke-rehearse

baseline:
	$(PY) -m tools.kschedlint --write-baseline $(LINT_PATHS)
