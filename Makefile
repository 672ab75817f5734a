# celestia-tpu developer targets.  `make lint` and the tier-1 pytest run
# (which includes tests/test_lint.py) are the review gates; the sanitizer
# target hardens the native pipeline whenever the toolchain allows.

PY ?= python
# machine-readable lint output: `make lint LINT_FORMAT=json` (or sarif)
# passes --format through; exit codes are unchanged either way
LINT_FORMAT ?=

.PHONY: lint lockwatch test chaos trace-smoke profile-smoke incident-smoke critpath-smoke multichip-smoke das-smoke swarm-smoke ingress-smoke device-resident-smoke mesh-live t1-budget native native-sanitize native-sanitize-tsan native-sanitize-asan

## celint: concurrency & determinism static analysis (exit 1 on findings)
lint:
	$(PY) -m celestia_tpu.lint $(if $(LINT_FORMAT),--format $(LINT_FORMAT))

## lock-order shadow checker over the tier-1 concurrency hammers: the
## runtime half of celint R6.  CELESTIA_TPU_LOCKWATCH=1 installs the
## watched-lock factories before any module lock is constructed; the
## session FAILS on any observed lock-order inversion (both acquisition
## stacks printed via the conftest gate)
lockwatch:
	CELESTIA_TPU_LOCKWATCH=1 JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_lockwatch.py tests/test_race.py tests/test_lru.py -q -m 'not slow' -p no:cacheprovider

## tier-1 test suite (same selection the CI driver runs)
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' -p no:cacheprovider

## seeded chaos suite: deterministic fault injection + recovery scenarios
## (fixed seeds; the same subset runs inside tier-1 via the plain test
## target — this entry is the focused robustness gate).  Reproduce any
## failure with CELESTIA_TPU_CHAOS_SEED / the seed in the test id.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_faults.py tests/test_chaos.py -q -m 'not slow' -p no:cacheprovider

## observability boot gate: one tiny-k testnode block with tracing on;
## asserts a non-empty, schema-valid Chrome trace (opens in Perfetto)
## and a line-by-line-parseable Prometheus exposition, then a 2-node
## merged-trace leg (two validator processes, one block, merged
## Perfetto timeline with a non-empty cross-node link)
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/trace_smoke.py

## device-observability boot gate: a traced tiny-k block must yield a
## schema-valid merged HOST+DEVICE Chrome trace (per-chip device track),
## an XLA cost row, a parseable >=2-snapshot time-series dump and one
## deliberately-tripped alert rule firing; then a one-node leg drives
## the real `query timeseries` / `query alerts` CLI against a
## synthetically height-stalled validator and scrapes plain-HTTP
## /metrics (tier-1 runs the same assertions via tests/test_profile_smoke.py)
profile-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/profile_smoke.py

## host-observability boot gate: a traced tiny-k validator with the
## host sampler + flight recorder armed is driven through one real
## block, then synthetically height-stalled with an injected stall
## rule — the alert firing must produce an on-disk incident bundle
## (valid manifest, Chrome trace with cat="sample" events on host
## thread tracks, non-empty folded stacks) retrievable via `query
## incident --out` against the live RPC; a second leg proves the
## disarmed path writes nothing and costs <1% (tier-1 runs the same
## assertions via tests/test_incident_smoke.py)
incident-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/incident_smoke.py

## block-lifecycle critical-path boot gate: one real block through a
## 2-node mesh must yield a non-empty critical path ending at
## rpc.cons_commit with the attribution partition (self + queue_wait +
## flow + gap) summing to the root wall within 1%, a POSITIVE
## propagation delay off the _tc send timestamp, a BlockScorecard row
## on both nodes and a named slowest validator in the mesh waterfall;
## a second leg injects a deliberately impossible block_e2e_slo budget
## (CELESTIA_TPU_SLO) and asserts the burn-rate firing transitions the
## flight recorder into a manifest-valid incident bundle carrying the
## offending trace (tier-1 runs the same assertions via
## tests/test_critpath_smoke.py)
critpath-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/critpath_smoke.py

## live mesh-path boot gate: a forced-multi-host-device subprocess
## drives one real block through prepare->process with the sharded
## extension wired in (CELESTIA_TPU_MESH) and asserts the merged trace
## carries the sharded dispatch span on >= 2 distinct per-chip device
## tracks and that the EDS cache served the process leg warm
multichip-smoke:
	$(PY) tools/multichip_smoke.py

## DA serving-plane boot gate: a tiny-k node serves a chunked multi-cell
## DasSampleBatch over the real gRPC boundary — every proof verifies
## against the data root (one pinned byte-identical to the per-cell
## prover), the das_rows cache answers the second pass warm, a saturated
## gate sheds the batch with retry_after_ms and the RetryPolicy client
## resumes, and the exposition stays parse-valid with the
## celestia_tpu_das_* counters present (tier-1 runs the same assertions
## via tests/test_das_smoke.py)
das-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/das_smoke.py

## swarm-scale serving crowd gate: ~64 seeded light clients (8 hostile
## over-askers) drive one live QoS-enabled node — light-tier p99 stays
## bounded and lane reservation holds while the hostile flood is demoted
## and shed, per-peer/per-lane exposition lines parse, and the
## swarm-induced fairness collapse fires das_fairness_floor whose
## transition dumps a valid flight-recorder incident bundle (tier-1 runs
## the same assertions via tests/test_swarm_smoke.py)
swarm-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/swarm_smoke.py

## batched tx-admission boot gate: a gossip TxPush flood (with a forged
## signature and a garbage blob buried mid-stream) drains through
## check_txs_batch on a live node — one verify_batch pass per chunk,
## replay admits nothing, block production takes the signer-grouped
## parallel FilterTxs leg and keeps every admitted tx, BroadcastBatch
## admits a follow-up batch over the wire, ingress.batch/ante.parallel
## spans land in the tracer and the celestia_tpu_ingress_* counters
## ride a parse-valid exposition (tier-1 runs the same assertions via
## tests/test_ingress_smoke.py)
ingress-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/ingress_smoke.py

## device-resident plane boot gate: one blob block prepared, processed
## and DAS-served with the plane FORCED on — the committed block is
## device-warm, every batched proof is byte-identical to the host
## reference, the merged transfer ledger shows no hot-path D2H beyond
## the data-root fetch + axis-roots fetch + proof-path gather, and
## celint R7 passes with zero host-sync allows in da/device_plane.py
## (tier-1 runs the same assertions via
## tests/test_device_resident_smoke.py)
device-resident-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/device_resident_smoke.py

## full live mesh-path suite (slow tier: each subprocess child pays one
## ~35-60 s structure-bound XLA CPU shard_map compile, over the 30 s
## tier-1 budget): live prepare->process byte-identity vs the
## single-device path, EDS-cache interop both directions, laundering
## rejection, divisibility fallback and the degradation ladder on a
## pure-row mesh, plus batched-vs-loop root equality and the warm-only
## state-sync leg on a data x row mesh
mesh-live:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_mesh_live.py -q -p no:cacheprovider

## tier-1 wall-time budget guard: judges the per-test durations file
## the last pytest session wrote (conftest) — fails loudly when any
## single non-slow test exceeded 30 s (the 870 s tier-1 run truncates)
t1-budget:
	$(PY) tools/t1_budget.py

## (re)build the production native library
native:
	$(PY) -c "from celestia_tpu.utils import native; assert native.available(), 'native build failed'"

## rebuild native/celestia_native.cpp under TSan and ASan+UBSan and re-run
## the thread-scaling byte-identity tests under each (loud SKIP when the
## toolchain lacks the sanitizer; hard failure otherwise)
native-sanitize:
	bash tools/native_sanitize.sh all

native-sanitize-tsan:
	bash tools/native_sanitize.sh tsan

native-sanitize-asan:
	bash tools/native_sanitize.sh asan

