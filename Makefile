# The verify recipe uses pipefail/PIPESTATUS (bash-only).
SHELL := /bin/bash

proto:
	protoc --python_out=elasticdl_tpu/proto -I elasticdl_tpu/proto elasticdl_tpu/proto/elasticdl_tpu.proto

# CPU-pinned so the suite is reproducible off-TPU (tests/conftest.py builds
# an 8-device virtual CPU platform on top of this).
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -x -q

# The tier-1 gate behind the static-analysis preamble: a lint failure
# fails verify before any test runs (the lint plane needs no jax and
# finishes in seconds). Bounded wall clock, collection errors tolerated,
# deterministic plugin set, pass-count echoed as the driver counts it.
verify: lint verify-tests

# The tier-1 window itself, lint-free (make ci runs lint as its own
# stage so the one-line summary attributes the failure to the right
# lane): the command the driver runs after every PR (`commands` in its
# TESTS_LAST_RUN.json) — six xdist workers, a file to a worker, 1,470 s,
# the pass count read from the junit file (the dots are the fallback).
# One process no longer reaches the suite's end inside any such window.
# The driver also sets ALLOW_MULTIPLE_LIBTPU_LOAD=1 in its own
# environment; the repo's files never do (tests/test_tpu_compile.py
# describes the chip inside a fixture, in the one worker given the file).
verify-tests:
	set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; said=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=$${said:-$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $$rc

# Kill orphaned edl process trees from earlier crashed runs (stale
# master heartbeats; tools/reap_orphans.py). Pre-step of every lane
# that launches real multi-process jobs — leftover workers squat on
# ports and CPU and starve the drills.
reap:
	-python tools/reap_orphans.py

# The unified static-analysis plane (tools/edl_lint, no jax import,
# seconds not minutes): concurrency (lock guards + ordering cycles),
# blocking-under-lock, jit-purity, compile-tracker, donation,
# hot-path-sync, mesh-spec-consistency, env-knob registry, proto
# drift, rpc deadlines, metric names, dead code — the last four ride
# the interprocedural dataflow engine (tools/edl_lint/dataflow.py).
# docs/STATIC_ANALYSIS.md has the rule catalog and the
# suppression/baseline workflow; a stale baseline entry fails the run.
# `lint-changed` restricts REPORTING to git-changed files for fast
# pre-commit runs (analysis always sees the whole program) and reuses
# the digest-keyed analysis cache when the tree is unchanged (<1 s).
lint:
	python -m tools.edl_lint

lint-changed:
	python -m tools.edl_lint --changed

# The chaos scenario suite (real multi-process jobs with injected faults;
# docs/ROBUSTNESS.md catalog) under a hard wall-clock cap.
chaos: reap
	set -o pipefail; timeout -k 10 900 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly

# The observability acceptance drills: real 2w+2PS jobs — one worker
# slowed by role-targeted chaos latency (edl_job_straggler + alert event
# + /api/summary), and one worker's READER slowed at the datapath.read
# local chaos point (input_starvation alert + datapath event trail +
# dominant-stage attribution + `edl dash --once --json`).
obs: reap
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/test_obs_aggregation.py -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly

# The fleet-telemetry smoke: hundreds of simulated pods (elasticdl_tpu/
# fleet) against a real master under seeded churn; asserts dispatch
# throughput, telemetry freshness, and O(1) endpoint bookkeeping.
fleet-smoke: reap
	set -o pipefail; timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly

# The policy acceptance drills (docs/POLICY.md catalog): real multi-
# process jobs where the self-healing engine must detect the fault AND
# throughput must recover — straggler blacklist, backup-task win,
# deadline scale-up with the world-hint handshake, preemption wave.
policy-drill: reap
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/test_policy_drill.py -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly

# The master-kill recovery drills (docs/ROBUSTNESS.md "Master recovery"):
# SIGKILL the master mid-job / mid-scale, relaunch over the same journal,
# and demand exactly-once records accounting plus the recovery trail.
master-drill: reap
	set -o pipefail; timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/test_master_drill.py -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly

native:
	python -c "from elasticdl_tpu import native; print(native.build())"

# The CI lane: lint -> tier-1 -> the drills, each stage runs even when
# an earlier one fails (one run answers "what is broken"), and the
# single trailing CI: line is the machine-readable verdict. Every stage
# runs on the CPU and claims correctness and counts only: what the
# system's speed is, benchmark/ measures on the chip (PERF.md).
ci:
	@lint=FAIL; tier1=FAIL; fleet=FAIL; obs=FAIL; policy=FAIL; master=FAIL; \
	set -o pipefail; lintlog=$$(mktemp); \
	$(MAKE) --no-print-directory lint 2>&1 | tee $$lintlog && lint=ok; \
	$(MAKE) --no-print-directory verify-tests && tier1=ok; \
	$(MAKE) --no-print-directory fleet-smoke && fleet=ok; \
	$(MAKE) --no-print-directory obs && obs=ok; \
	$(MAKE) --no-print-directory policy-drill && policy=ok; \
	$(MAKE) --no-print-directory master-drill && master=ok; \
	rules=$$(grep -ao 'per-rule: .*' $$lintlog | tail -1); rm -f $$lintlog; \
	echo "CI: lint=$$lint tier1=$$tier1 fleet=$$fleet obs=$$obs policy=$$policy master=$$master$${rules:+ [$$rules]}"; \
	[ "$$lint" = ok ] && [ "$$tier1" = ok ] && [ "$$fleet" = ok ] && [ "$$obs" = ok ] && [ "$$policy" = ok ] && [ "$$master" = ok ]

.PHONY: proto test verify verify-tests reap lint lint-changed chaos obs fleet-smoke policy-drill master-drill native ci
