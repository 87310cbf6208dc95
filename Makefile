# Developer entry points. Tier-1 CI runs `make lint` (graftlint gate,
# also enforced by tests/test_graftlint.py) and `make test`.

.PHONY: lint lint-fast lint-json lint-sarif lint-ci test chaos obs-demo \
	bench bench-bytes bench-oocore bench-elastic serve-demo multihost \
	autoscale-sim usage-demo doctor doctor-demo bench-regress

# the full interprocedural pass (JX001-JX019, concurrency + abstract
# shape/sharding rules included); fails on any finding not grandfathered
# in baseline.json (which a PR may shrink, never grow). The tail line
# prints the top-3 slowest rules so rule authors see their cost.
lint:
	python -m cycloneml_tpu.analysis cycloneml_tpu \
	    --baseline cycloneml_tpu/analysis/baseline.json

# incremental gate for the edit loop: full call-graph facts, but checks
# and reports only files changed per `git diff` plus their (transitive)
# callers' modules (parse cache reused)
lint-fast:
	python -m cycloneml_tpu.analysis --changed \
	    --baseline cycloneml_tpu/analysis/baseline.json

lint-json:
	python -m cycloneml_tpu.analysis cycloneml_tpu \
	    --baseline cycloneml_tpu/analysis/baseline.json --json

# SARIF 2.1.0 for CI/code-review inline rendering
lint-sarif:
	python -m cycloneml_tpu.analysis cycloneml_tpu \
	    --baseline cycloneml_tpu/analysis/baseline.json --sarif

# the CI job: full run, SARIF artifact at a stable path
# (artifacts/graftlint.sarif; override GRAFTLINT_SARIF_OUT), parse cache
# relocatable via CYCLONE_LINT_CACHE, nonzero exit on any unsuppressed
# finding
lint-ci:
	bash scripts/ci_lint.sh

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
	    --continue-on-collection-errors -p no:cacheprovider

chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q \
	    -p no:cacheprovider

# the 2-process deploy/multihost harness standalone: real Master/Worker
# daemons, real jax.distributed rendezvous, the kill-a-worker recovery
# loop. Hard timeout: a wedged cross-process rendezvous must kill the
# run loudly, never hang CI.
multihost:
	timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
	    tests/test_multihost.py tests/test_deploy.py -q \
	    -p no:cacheprovider

# small traced fit -> exported Chrome trace -> schema + profile validation
obs-demo:
	JAX_PLATFORMS=cpu python scripts/obs_demo.py

# usage-attribution acceptance: two scoped jobs (a fit + a serving
# storm), per-scope device-seconds/FLOPs/bytes must sum to the global
# ledger within 1% and /api/v1/usage must serve both rows
usage-demo:
	JAX_PLATFORMS=cpu python scripts/usage_demo.py

# one JSON line: e2e LR throughput + phases + the multi-class OvR
# stacked-vs-serial comparison (ovr_stacked_speedup, models_per_compile).
# Tee'd to artifacts/ so `make bench bench-regress` gates the run it made.
bench:
	@mkdir -p artifacts
	python bench.py | tee artifacts/bench_last.json

# regression sentinel: ingest artifacts/bench_last.json (if present) into
# the append-only artifacts/bench_history.jsonl ledger and judge each
# metric's newest row against median+MAD of its comparable history
# (cyclone.regress.*) — nonzero on any regression. The ledger starts
# empty on a fresh checkout. Self-test of the gate itself, on synthetic
# rows only: `... bench_regress.py --inject-regression`
bench-regress:
	python scripts/bench_regress.py --ingest artifacts/bench_last.json

# offline bottleneck diagnosis over a Chrome trace or flight dump:
# make doctor TRACE=artifacts/trace.json — exit 2 when anything fires
doctor:
	python -m cycloneml_tpu.observe.doctor $(TRACE)

# performance-doctor acceptance: clean warm fit => ZERO findings,
# pathological fit (forced recompiles + delayed staging lane + 1-byte
# shard cache) => >= 4 distinct evidence-backed finding kinds, and the
# doctor CLI --json byte-identical across two runs over the same trace
doctor-demo:
	JAX_PLATFORMS=cpu python scripts/doctor_demo.py

# standalone sweep-byte check, BOTH narrow legs: the bf16 data-tier
# sweep must access < 60% of the fp32 sweep's bytes and the fp8 (e4m3)
# sweep < 45% (measured ~0.35 at n=4096 d=256) — XLA cost-analysis
# ground truth, lower-only
bench-bytes:
	python scripts/bench_bytes.py

# out-of-core acceptance: streamed vs in-core wall time, epoch sweep
# bytes + O(shard) peak via costs.streamed_sweep_cost, and the
# transfer/compute overlap fraction from the stream spans — exits
# nonzero if overlap < 30% on the 8-device CPU smoke
bench-oocore:
	python scripts/bench_oocore.py

# elastic acceptance: time-to-resume for the same full->half mesh
# transition, reshard-in-place (memory) vs checkpoint round-trip
# (disk + sha256) on the 8-device CPU smoke — exits nonzero unless the
# reshard path is strictly faster
bench-elastic:
	python scripts/bench_elastic.py

# serving acceptance demo: 2 models, concurrent request storm, asserts
# compile-count == bucket-count and p99 under the window bound
serve-demo:
	JAX_PLATFORMS=cpu python scripts/serve_demo.py

# autoscale control-plane gate: replay the committed signal trace
# through the production policy twice — byte-identical logs
# (determinism) AND byte-equal to the committed golden (drift). A diff
# here IS the policy-change review artifact; regenerate deliberately
# with `python scripts/autoscale_sim.py --update`. Pure host-side, <1s.
autoscale-sim:
	python scripts/autoscale_sim.py
