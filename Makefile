.PHONY: install test test-multihost test-resilience test-obs test-plan test-lowering test-cache test-delta test-shuffle test-exchange test-serve test-dist test-views test-analysis test-tuning lint-locks cache-clean trace-smoke telemetry-smoke timeline-smoke serve-smoke fleet-smoke dist-smoke view-smoke bench bench-smoke dryrun native

# editable install so examples/notebooks import fugue_tpu without PYTHONPATH
# (--no-build-isolation: the env is offline; the baked-in setuptools builds it)
install:
	pip install -e . --no-deps --no-build-isolation

# the three smoke gates below are non-blocking in `make test` (their
# dedicated targets stay blocking) — but a failure must never be SILENT:
# each emits a one-line WARNING so a regressed chaos/perf gate is visible
# in CI logs instead of scrolling past as an ignored make error.
# dist-smoke is BLOCKING (ISSUE 16): workflow.run now routes through the
# dist tier, so its chaos ladder is tier-1 behavior; set
# DIST_SMOKE_NONBLOCKING=1 to demote it back to a report while iterating
# on a known dist change
test:
	python -m pytest tests/ -q
	python tools/lint_locks.py --strict         # concurrency audit; BLOCKING (ISSUE 12)
	-@$(MAKE) --no-print-directory bench-smoke  || echo "WARNING: bench-smoke FAILED (non-blocking in 'make test'); run 'make bench-smoke' to reproduce"
	-@$(MAKE) --no-print-directory serve-smoke  || echo "WARNING: serve-smoke FAILED (non-blocking in 'make test'); run 'make serve-smoke' to reproduce"
	-@$(MAKE) --no-print-directory fleet-smoke  || echo "WARNING: fleet-smoke FAILED (non-blocking in 'make test'); run 'make fleet-smoke' to reproduce"
	-@$(MAKE) --no-print-directory view-smoke   || echo "WARNING: view-smoke FAILED (non-blocking in 'make test'); run 'make view-smoke' to reproduce"
	-@$(MAKE) --no-print-directory timeline-smoke || echo "WARNING: timeline-smoke FAILED (non-blocking in 'make test'); run 'make timeline-smoke' to reproduce"
	@if [ "$$DIST_SMOKE_NONBLOCKING" = "1" ]; then \
	  $(MAKE) --no-print-directory dist-smoke || echo "WARNING: dist-smoke FAILED (demoted by DIST_SMOKE_NONBLOCKING=1); run 'make dist-smoke' to reproduce"; \
	else \
	  $(MAKE) --no-print-directory dist-smoke; \
	fi

# downsized CPU-only perf gate (≤~30s, 8-device CPU mesh): fails when the
# oracle-normalized groupby_aggregate vs_baseline drops >20% below the
# recorded value (BENCH_SMOKE_BASELINE.json for this env, else BENCH_r05).
# --compare is a BLOCKING gate (exit 8 on any metric regression vs the
# committed smoke baseline); set BENCH_COMPARE_NONBLOCKING=1 to demote it
# back to a report while iterating on a known perf change
bench-smoke:
	python bench.py --smoke
	@if [ "$$BENCH_COMPARE_NONBLOCKING" = "1" ]; then \
	  python bench.py --compare BENCH_SMOKE_BASELINE.json || true; \
	else \
	  python bench.py --compare BENCH_SMOKE_BASELINE.json; \
	fi

# large-scale proofs (100M-row streaming, 100Mx1M join) — excluded from the
# default run by addopts='-m "not slow"'; the explicit -m here overrides it
test-slow:
	python -m pytest tests/ -q -m slow

# the multihost job: engine verbs + collectives across a REAL 2-process
# jax.distributed mesh (each worker is its own OS process)
test-multihost:
	python -m pytest tests/core/test_multihost.py -q -m "slow or not slow"

# fault-injection suite (docs/resilience.md): worker SIGKILL recovery,
# chunk deadlines, poison quarantine, RPC retry, checkpoint-aware replay.
# not marked slow — tier-1 runs it too; this target is the focused loop
test-resilience:
	JAX_PLATFORMS=cpu python -m pytest tests/core/test_resilience.py -q -m "not slow"

# observability suite (docs/observability.md): span-tree shape, Chrome
# trace export, disabled-path overhead guard, fork-boundary round trip
test-obs:
	JAX_PLATFORMS=cpu python -m pytest tests/obs -q -m "not slow"

# plan-optimizer suite (docs/plan.md): optimized-vs-unoptimized parity
# (bit-identical), pruning-reaches-producer spies, fusion span shape,
# UDF no-op guard, conf gates. Part of `make test` (tests/ includes it)
test-plan:
	JAX_PLATFORMS=cpu python -m pytest tests/plan -q -m "not slow"

# segment-lowering suite (docs/plan.md): lowered-vs-unlowered parity
# across aggregate/take/distinct/join/SQL (bounded + streaming), refusal
# fallback span/result identity, plan.segment span shape + one jit entry
# per segment, conf gate, explain rendering
test-lowering:
	JAX_PLATFORMS=cpu python -m pytest tests/plan/test_lowering.py -q -m "not slow"

# out-of-core shuffle suite (docs/shuffle.md): in-device exchange tests
# plus the spill path — spill-vs-legacy join parity (dup/NULL keys, all
# hash-partitionable types), bounded peak_device_bytes at 10x the budget,
# hash-repartition round trip, torn-spill recovery, conf gates
test-shuffle:
	JAX_PLATFORMS=cpu python -m pytest tests/jax_engine/test_shuffle.py -q -m "not slow"

# device-resident staged exchange suite (docs/shuffle.md
# "device_exchange"): rung parity vs spill and the legacy ladder across
# dup/NULL/-0.0/tz-aware keys, kill-switch bit-identity with identical
# engine-verb span multisets, over-budget forced spill fallback, the
# staged-schedule peak-stage-bytes bound from the high-water gauge, and
# the mem-bucket decoded-form ingest cache
test-exchange:
	JAX_PLATFORMS=cpu python -m pytest tests/jax_engine/test_device_exchange.py -q -m "not slow"

# result-cache suite (docs/cache.md): cached-hit parity, invalidation
# (mutated files / edited UDFs / partition specs), poisoned-subtree
# refusal, publish races, torn artifacts, persist-across-restart — plus
# the partition-level delta suite (test-delta below)
test-cache:
	JAX_PLATFORMS=cpu python -m pytest tests/cache -q -m "not slow"

# partition-level incremental recompute suite (docs/cache.md "Incremental
# recompute"): grown-source delta parity across fused-chain / filter /
# dense-aggregate shapes × jax/native engines × optimizer on/off, the
# refusal ladder (changed contents, reordered partitions, non-row-local
# verbs), grown single-file append detection, manifest/eviction
# consistency, two-process append races, persist of delta-merged frames
test-delta:
	JAX_PLATFORMS=cpu python -m pytest tests/cache/test_delta_cache.py -q -m "not slow"

# UDF static-analysis suite (docs/analysis.md): translated-vs-interpreted
# parity across engines × optimizer on/off × bounded/streaming, the
# refusal matrix (globals, mutable closures, .apply, loops, unknown
# methods, non-determinism — each bit-identical with the reason rendered
# in explain()), pruning-reaches-producer under analyzed UDFs, delta
# serving of analyzed row-local chains, fingerprint invalidation on edit,
# workflow.lint() diagnostics, analysis counters + /metrics exposition
test-analysis:
	JAX_PLATFORMS=cpu python -m pytest tests/analysis -q -m "not slow"

# cost-based adaptive execution suite (docs/tuning.md): the _tuned.json
# lifecycle (atomic publish under a two-process race, corrupt file →
# defaults with ONE warning, stale-fingerprint eviction), the adjustment
# policy units, kill-switch bit-identity, per-stream pipeline stats,
# explain()/stats()/metrics rendering, and warm-run convergence
test-tuning:
	JAX_PLATFORMS=cpu python -m pytest tests/tuning -q -m "not slow"

# repo concurrency lint (ISSUE 10 audit as a repeatable AST check): flags
# writes to shared-engine mutable attributes outside the audited lock
# helpers. Zero findings since ISSUE 12 — `make test` enforces it with
# --strict (blocking); this target stays the report-only loop
lint-locks:
	python tools/lint_locks.py

# multi-tenant serving suite (docs/serving.md): admission queue + tenant
# budgets + priority aging, plan-fingerprint single-flight (one shared
# execution, cancel-safe waiters), the /serve/* RPC surface with
# idempotency keys, /healthz-vs-/readyz split, and the shared-engine
# concurrency regression hammer (bit-identical results, coherent counters)
test-serve:
	JAX_PLATFORMS=cpu python -m pytest tests/serve -q -m "not slow"

# serving load gate (ISSUE 10 acceptance, exit 12): 8 concurrent clients
# × 4 tenants × mixed workloads (cached hit / broadcast join / streaming
# aggregate / delta append) through ONE EngineServer — zero failed
# submissions, dedup_hits >= 1 with shared executions, per-tenant
# p50/p99 + rows/s, results bit-identical to serial cache-off runs
serve-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-smoke

# fleet chaos gate (ISSUE 13 acceptance, exit 15): 3 EngineServer
# processes sharing a store + journal dir behind a FleetClient; one
# replica SIGKILLed mid-execution — every submission completes (failover
# under the same idempotency key), the journal audit shows ZERO duplicate
# completed executions, >= 1 cross-replica dedup hit and >= 1 claim-lease
# steal observed, results bit-identical to a serial cache-off oracle
fleet-smoke:
	JAX_PLATFORMS=cpu python bench.py --fleet-smoke

# distributed worker-tier suite (docs/distributed.md): heartbeat
# freshness/staleness, lease acquire/renew/steal (expiry + heartbeat +
# pid-fallback matrix), end-to-end dist-vs-serial bit-identity, lease
# expiry mid-task re-dispatch, speculative duplicate publish (one done
# record, one artifact), supervisor restart over in-flight leases,
# remote fragment fetch + orphaned-output recovery, fault sites
test-dist:
	JAX_PLATFORMS=cpu python -m pytest tests/distributed -q -m "not slow"

# worker-tier chaos gate (ISSUE 14 acceptance, exit 16): 3 DistWorker
# processes + supervisor run a distributed load→shuffle-join→aggregate;
# the worker holding the straggler map lease is SIGKILLed mid-shuffle —
# all partitions complete via heartbeat-proven lease re-dispatch, the
# bucket audit shows ZERO lost/double-counted rows, and the result is
# bit-identical to the single-process cache-off oracle (the
# fugue.tpu.dist.enabled=false kill-switch path)
dist-smoke:
	JAX_PLATFORMS=cpu python bench.py --dist-smoke

# continuous-view suite (docs/views.md): registration WAL replay after a
# SIGKILLed registrar, per-generation bit-identity, delta refusal
# degrading to full recompute, watch-lease steal to a survivor replica,
# unregister tombstones, freshness-SLO admission boost, typed-event
# counter parity, and the fleet LRU pinning each view's latest generation
test-views:
	JAX_PLATFORMS=cpu python -m pytest tests/views -q -m "not slow"

# continuous-view chaos gate (ISSUE 20 acceptance, exit 20): 2 replicas
# share a store + journal; a view over a source grown one partition per
# round for 5 rounds is maintained while the lease-holding replica is
# SIGKILLed mid-refresh — the survivor steals the watch lease, every
# generation publishes exactly once with correct as_of, the final result
# is bit-identical to a cold cache-off oracle, and the delta path keeps
# steady-state skip_fraction >= 0.9 (no silent full recomputes)
view-smoke:
	JAX_PLATFORMS=cpu python bench.py --view-smoke

# wipe a result-cache directory's artifacts: make cache-clean CACHE_DIR=...
# (defaults to $FUGUE_TPU_CACHE_DIR)
cache-clean:
	python -c "import os; from fugue_tpu.cache import clean_cache_dir; \
	  print(clean_cache_dir('$(CACHE_DIR)' or os.environ.get('FUGUE_TPU_CACHE_DIR', '')))"

# end-to-end trace proof: run the traced smoke workflow, then assert the
# exported file is valid Chrome trace-event JSON (Perfetto-loadable)
trace-smoke:
	python bench.py --smoke --trace /tmp/fugue_trace_smoke
	python -c "from fugue_tpu.obs import validate_chrome_trace; \
	  s = validate_chrome_trace('/tmp/fugue_trace_smoke/trace.json'); \
	  print('trace OK:', s['spans'], 'spans,', s['events'], 'events')"

# live-telemetry round trip (docs/observability.md): run a small traced +
# sampled streaming workflow with /metrics bound to the engine, scrape it
# while the run is in flight, validate the Prometheus exposition, and
# assert the exported trace carries device_bytes/overlap_fraction
# Perfetto counter tracks
telemetry-smoke:
	JAX_PLATFORMS=cpu python bench.py --telemetry-smoke /tmp/fugue_telemetry_smoke

# cluster-tracing chaos gate (ISSUE 18 acceptance, exit 19): the dist
# chaos shape (3 workers, straggler's holder SIGKILLed mid-shuffle) with
# tracing + span spools + the flight recorder ON — the spools assemble
# into ONE validated Perfetto trace with >= 4 named process tracks whose
# worker spans share the run's trace id, and the kill is reconstructed
# FROM THE EVENT LOG ALONE (chaos.inject → hb.expired → lease.steal →
# task.redispatch, in order) by tools/fugue_timeline.py
timeline-smoke:
	JAX_PLATFORMS=cpu python bench.py --timeline-smoke /tmp/fugue_timeline_smoke

bench:
	python bench.py

dryrun:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 python -c "import jax; jax.config.update('jax_platforms','cpu'); import __graft_entry__ as g; g.dryrun_multichip(8)"

native:
	python -c "from fugue_tpu.native import build; assert build(force=True)"
