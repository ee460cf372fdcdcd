"""Configuration keys and global defaults.

Parity with the reference's conf surface (``/root/reference/fugue/constants.py``)
plus TPU-engine keys. All keys use the ``fugue.`` prefix so user code written
against the reference conf names keeps working.
"""

from typing import Any, Dict

from ._utils.params import ParamDict

KEYWORD_ROWCOUNT = "ROWCOUNT"
KEYWORD_CONCURRENCY = "CONCURRENCY"
KEYWORD_PARALLELISM = "PARALLELISM"

FUGUE_ENTRYPOINT = "fugue.plugins"

FUGUE_CONF_WORKFLOW_CONCURRENCY = "fugue.workflow.concurrency"
FUGUE_CONF_WORKFLOW_CHECKPOINT_PATH = "fugue.workflow.checkpoint.path"
FUGUE_CONF_WORKFLOW_AUTO_PERSIST = "fugue.workflow.auto_persist"
FUGUE_CONF_WORKFLOW_AUTO_PERSIST_VALUE = "fugue.workflow.auto_persist_value"
FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE = "fugue.workflow.exception.hide"
FUGUE_CONF_WORKFLOW_EXCEPTION_INJECT = "fugue.workflow.exception.inject"
FUGUE_CONF_WORKFLOW_EXCEPTION_OPTIMIZE = "fugue.workflow.exception.optimize"
FUGUE_CONF_SQL_IGNORE_CASE = "fugue.sql.compile.ignore_case"
FUGUE_CONF_SQL_DIALECT = "fugue.sql.compile.dialect"
FUGUE_CONF_DEFAULT_PARTITIONS = "fugue.default.partitions"
FUGUE_CONF_CACHE_PATH = "fugue.workflow.cache.path"

# TPU-engine specific
FUGUE_TPU_CONF_MESH_SHAPE = "fugue.tpu.mesh_shape"
FUGUE_TPU_CONF_ROW_AXIS = "fugue.tpu.row_axis"
FUGUE_TPU_CONF_DEFAULT_BATCH_ROWS = "fugue.tpu.default_batch_rows"
# cap on O(shards x groups) partial-row transfers (distinct cardinality guard)
FUGUE_TPU_CONF_MAX_PARTIAL_ROWS = "fugue.tpu.max_partial_rows"
# debug: cross-check compiled shard_map transformers against the masked
# reference on shard 0 (catches UDFs ignoring the __valid__ contract)
FUGUE_TPU_CONF_VALIDATE_COMPILED = "fugue.tpu.validate_compiled"
# fork-pool size for the general (host pandas) UDF map path; -1 = auto
# (the engine's get_current_parallelism), 0/1 = serial
FUGUE_TPU_CONF_MAP_PARALLELISM = "fugue.tpu.map.parallelism"
# frames below this row count always map serially (pool setup ~100ms)
FUGUE_TPU_CONF_MAP_PARALLEL_MIN_ROWS = "fugue.tpu.map.parallel_min_rows"
# max dense segment-id space for the sort-free keyed compiled map plan
FUGUE_TPU_CONF_DENSE_MAP_RANGE = "fugue.tpu.map.dense_range"
# keep the ingestion arrow table alive on JaxDataFrames for zero-cost host
# reads (global conf; ~2x host memory on ingest-heavy pipelines when True)
FUGUE_TPU_CONF_INGEST_CACHE = "fugue.tpu.ingest_cache"
# --- resilience layer (see fugue_tpu/resilience and docs/resilience.md) ---
# retry policy for fork-pool map chunks: attempts (1 disables retry),
# exponential backoff base/multiplier/cap (seconds) and jitter fraction
FUGUE_TPU_CONF_RETRY_ATTEMPTS = "fugue.tpu.retry.attempts"
FUGUE_TPU_CONF_RETRY_BASE = "fugue.tpu.retry.base"
FUGUE_TPU_CONF_RETRY_MULTIPLIER = "fugue.tpu.retry.multiplier"
FUGUE_TPU_CONF_RETRY_MAX_BACKOFF = "fugue.tpu.retry.max_backoff"
FUGUE_TPU_CONF_RETRY_JITTER = "fugue.tpu.retry.jitter"
# per-workflow-task retry attempts (default 1 = fail fast, matching the
# reference); retried tasks re-consult StrongCheckpoint.exists so finished
# upstream work replays from disk instead of recomputing
FUGUE_TPU_CONF_RETRY_TASK_ATTEMPTS = "fugue.tpu.retry.task.attempts"
# HTTP RPC client retry attempts (connect-phase failures and idempotent
# calls only — a request that may have reached the server is never blindly
# re-sent)
FUGUE_TPU_CONF_RETRY_RPC_ATTEMPTS = "fugue.tpu.retry.rpc.attempts"
# per-chunk wall-clock deadline (seconds) on the fork-pool map path;
# 0/unset = unbounded
FUGUE_TPU_CONF_MAP_CHUNK_TIMEOUT = "fugue.tpu.map.chunk_timeout"
# fault-injection plan (see fugue_tpu/resilience/fault.py for the grammar);
# also settable via the FUGUE_TPU_FAULT_PLAN env var
FUGUE_TPU_CONF_FAULT_PLAN = "fugue.tpu.fault.plan"
# HTTP RPC client socket timeouts (seconds)
FUGUE_RPC_CONF_HTTP_CONNECT_TIMEOUT = "fugue.rpc.http_client.connect_timeout"
FUGUE_RPC_CONF_HTTP_READ_TIMEOUT = "fugue.rpc.http_client.read_timeout"

# --- observability (see fugue_tpu/obs and docs/observability.md) ---
# master switch for the hierarchical span tracer (workflow task → engine
# verb → streaming chunk / map worker attempt); the FUGUE_TPU_TRACE env
# var overrides this in both directions. Disabled costs ~an attribute
# check per instrumented site.
FUGUE_TPU_CONF_TRACE_ENABLED = "fugue.tpu.trace.enabled"
# mirror host spans into the XLA timeline via jax.profiler.TraceAnnotation
# so device and host spans line up in a Perfetto capture (default True;
# only active while tracing is enabled)
FUGUE_TPU_CONF_TRACE_XLA = "fugue.tpu.trace.xla"
# directory to auto-export a Chrome trace-event JSON into after every
# workflow run (one file per run); empty/unset = no auto-export
FUGUE_TPU_CONF_TRACE_DIR = "fugue.tpu.trace.dir"
# span buffer cap; past it new spans are dropped (and counted as dropped)
FUGUE_TPU_CONF_TRACE_MAX_SPANS = "fugue.tpu.trace.max_spans"
# shared spool directory for cluster tracing (ISSUE 18): remote processes
# (dist workers, serve replicas) atomically publish their span buffer +
# resource-sampler ring there as <host>-<pid>.spool.json, and
# obs.assemble_trace merges the spools into ONE Perfetto trace with one
# named track per process. Empty/unset = no spooling (the default; spool
# writes only happen while tracing is also enabled)
FUGUE_TPU_CONF_TRACE_SPOOL_DIR = "fugue.tpu.trace.spool_dir"

# --- cluster flight recorder (fugue_tpu/obs/events.py; ISSUE 18) ---
# master switch for the append-only recovery-event log: lease
# acquire/renew/steal, heartbeat expiry, re-dispatch, orphan
# invalidation, speculative twins, fleet failovers, journal replays —
# typed JSON records carrying the cluster trace id, rendered by
# workflow.timeline() / tools/fugue_timeline.py. Default OFF; the
# FUGUE_TPU_EVENTS env var overrides in both directions. Disabled cost
# is one attribute check per recovery event (which are rare by nature).
FUGUE_TPU_CONF_EVENTS_ENABLED = "fugue.tpu.events.enabled"
# shared directory the per-process event files append into
# (<host>-<pid>.events.jsonl); FUGUE_TPU_EVENTS_DIR env overrides
FUGUE_TPU_CONF_EVENTS_DIR = "fugue.tpu.events.dir"

# --- live telemetry (fugue_tpu/obs/sampler.py + /metrics; ISSUE 6) ---
# master switch for the continuous resource sampler: a daemon thread
# recording device bytes, host RSS, jit/result-cache occupancy and
# pipeline overlap_fraction into a bounded ring buffer — exported as
# Perfetto counter tracks and /metrics gauges. Default OFF; the
# FUGUE_TPU_TELEMETRY env var overrides in both directions. Enabled
# costs <2% (a handful of cheap probes per interval); disabled there is
# no thread at all.
FUGUE_TPU_CONF_TELEMETRY_ENABLED = "fugue.tpu.telemetry.enabled"
# seconds between resource samples (default 0.25)
FUGUE_TPU_CONF_TELEMETRY_INTERVAL = "fugue.tpu.telemetry.interval"
# ring buffer capacity in samples (default 4096; oldest samples drop)
FUGUE_TPU_CONF_TELEMETRY_RING = "fugue.tpu.telemetry.ring_size"
# value of the `workflow` label attached to every span-histogram sample
# during a run (default: a stable 8-hex hash of the workflow's task
# uuids) — the per-tenant attribution key of the future serving layer
FUGUE_TPU_CONF_TELEMETRY_WORKFLOW = "fugue.tpu.telemetry.workflow"

# streaming (out-of-core) execution: rows per host->device chunk; the
# device working set is O(chunk_rows x columns), NOT O(dataset)
FUGUE_TPU_CONF_STREAM_CHUNK_ROWS = "fugue.tpu.stream.chunk_rows"
# depth of the background ingest pipeline's chunk queue (see
# fugue_tpu/jax/pipeline.py and docs/streaming.md): host decode + H2D of
# the NEXT chunks overlap device compute on the CURRENT one; device working
# set grows to O((depth+1) x chunk). 0 disables (strictly serial chunks)
FUGUE_TPU_CONF_STREAM_PREFETCH_DEPTH = "fugue.tpu.stream.prefetch_depth"
# "lo,hi" inclusive int key range for streaming dense aggregates; without
# it the range is probed from the FIRST chunk only, and any later
# out-of-range key raises (one-pass streams can't be re-scanned)
FUGUE_TPU_CONF_STREAM_KEY_RANGE = "fugue.tpu.stream.key_range"

# logical plan optimizer (fugue_tpu/plan, docs/plan.md): rewrites the task
# DAG at workflow.run() time. The master switch gates all passes; each pass
# can also be disabled individually. All default ON; every rewrite is
# result-identical to the unoptimized path (tests/plan/test_optimizer.py).
# plan.* keys are per-run compile switches: workflow.run() honors them from
# engine conf, run conf AND workflow compile_conf, without writing the
# compile_conf values back into a (possibly shared) engine's conf.
FUGUE_TPU_CONF_PLAN_PREFIX = "fugue.tpu.plan."
FUGUE_TPU_CONF_PLAN_OPTIMIZE = "fugue.tpu.plan.optimize"
# column pruning: push projections into create/load/stream producers so
# columns no downstream task reads are never decoded or H2D-transferred
FUGUE_TPU_CONF_PLAN_PRUNE = "fugue.tpu.plan.prune"
# filter pushdown: hoist filters through projections/renames/joins toward
# the producer so invalid rows are masked before device work
FUGUE_TPU_CONF_PLAN_PUSHDOWN = "fugue.tpu.plan.pushdown"
# verb fusion: collapse adjacent select/filter/assign chains into one
# FusedVerbs task (single jitted step on the jax engine; per-chunk on
# streams)
FUGUE_TPU_CONF_PLAN_FUSE = "fugue.tpu.plan.fuse"
# whole-plan SPMD segment lowering (docs/plan.md): after fusion, collapse
# maximal device-resident segments — a row-local verb chain flowing into a
# dense aggregate / take / distinct / broadcast-join probe — into ONE
# LoweredSegment task the jax engine compiles to a single shard_map-
# partitioned XLA program (per-segment fallback to the per-verb path on
# any lowering refusal keeps results bit-identical)
FUGUE_TPU_CONF_PLAN_LOWER_SEGMENTS = "fugue.tpu.plan.lower_segments"

# UDF static analysis (fugue_tpu/analysis, docs/analysis.md): AST-trace
# plain-Python pandas UDFs into exact column read/write sets, purity/
# row-locality verdicts, and (for the recognized shape subset) a
# translation into the compiled step pipeline. analyze_udfs=false
# restores the fully conservative pre-analysis treatment inside the
# optimizer (UDFs demand ALL columns, never translate);
# translate_udfs=false keeps the facts (pruning/pushdown still commute
# through analyzed UDFs) but always runs UDFs on the interpreted path.
# Both default ON; every refusal is bit-identical by construction. The
# plan.* prefix keeps them per-run compile switches (never written into a
# shared engine's conf).
FUGUE_TPU_CONF_PLAN_ANALYZE_UDFS = "fugue.tpu.plan.analyze_udfs"
FUGUE_TPU_CONF_PLAN_TRANSLATE_UDFS = "fugue.tpu.plan.translate_udfs"

# content-addressed result cache (fugue_tpu/cache, docs/cache.md): memoize
# task outputs ACROSS runs, keyed on canonical post-optimization plan
# fingerprints. Master switch (default ON — with no cache.dir the cache is
# memory-only and scoped to one engine); =false is byte-for-byte the
# pre-cache execution path.
FUGUE_TPU_CONF_CACHE_ENABLED = "fugue.tpu.cache.enabled"
# artifact-store directory (shared across processes; atomic publishes).
# Empty/unset = no disk tier. The FUGUE_TPU_CACHE_DIR env var is the
# fallback when the conf key is unset. An unwritable dir degrades the
# cache to memory-only with a single warning.
FUGUE_TPU_CONF_CACHE_DIR = "fugue.tpu.cache.dir"
# byte budget of the in-process LRU over live result frames
FUGUE_TPU_CONF_CACHE_MEM_BYTES = "fugue.tpu.cache.mem_bytes"
# size cap of the on-disk artifact store; LRU-evicted past it
FUGUE_TPU_CONF_CACHE_DISK_BYTES = "fugue.tpu.cache.disk_bytes"
# frames larger than this are never written to the disk tier (still
# memory-cached when they fit the mem budget)
FUGUE_TPU_CONF_CACHE_MAX_ARTIFACT_BYTES = "fugue.tpu.cache.max_artifact_bytes"
# CreateData tables above this are REFUSED (poisoned), not content-hashed
FUGUE_TPU_CONF_CACHE_FINGERPRINT_MAX_BYTES = "fugue.tpu.cache.fingerprint_max_bytes"
# free-form namespace mixed into every fingerprint: bump it to invalidate
# all entries without deleting files
FUGUE_TPU_CONF_CACHE_SALT = "fugue.tpu.cache.salt"
# partition-level incremental recompute (docs/cache.md "Incremental
# recompute"): a warm run over a GROWN Load source recomputes only the new
# partitions and merges with the cached result/partial accumulator.
# Default ON; =false restores the all-or-nothing whole-task cache.
FUGUE_TPU_CONF_CACHE_DELTA_ENABLED = "fugue.tpu.cache.delta.enabled"
# artifact-COUNT cap of the on-disk store (per-partition delta artifacts
# multiply small files; bytes alone don't bound inode pressure). mtime-LRU
# evicted past it, alongside the disk_bytes cap. 0 = unlimited.
FUGUE_TPU_CONF_CACHE_DISK_MAX_ENTRIES = "fugue.tpu.cache.disk_max_entries"

# out-of-core hash shuffle (fugue_tpu/shuffle, docs/shuffle.md): spill
# key-hash buckets to disk, then join/repartition bucket-at-a-time so
# inputs FAR past device memory complete under a bounded device working
# set. Master switch (default ON; =false restores the pre-shuffle ladder:
# broadcast / in-device copartition / host fallback).
FUGUE_TPU_CONF_SHUFFLE_ENABLED = "fugue.tpu.shuffle.enabled"
# spill-file directory; unset = <tempdir>/fugue_tpu_shuffle. Each shuffle
# creates a unique subdirectory, removed on success AND on failure.
FUGUE_TPU_CONF_SHUFFLE_DIR = "fugue.tpu.shuffle.dir"
# explicit bucket count P (0 = auto from size estimate / bucket_bytes)
FUGUE_TPU_CONF_SHUFFLE_BUCKETS = "fugue.tpu.shuffle.buckets"
# target on-disk bytes per bucket when auto-sizing P; each bucket pair
# must fit the device budget TOGETHER with the join's intermediates, so
# keep this a small fraction (default 1/32) of device_budget_bytes
FUGUE_TPU_CONF_SHUFFLE_BUCKET_BYTES = "fugue.tpu.shuffle.bucket_bytes"
# the device byte budget joins must stay under: size estimates past it
# pick the spill-shuffle strategy. 0/unset = auto (device memory stats
# when the backend reports them, else half of host MemTotal).
FUGUE_TPU_CONF_SHUFFLE_DEVICE_BUDGET = "fugue.tpu.shuffle.device_budget_bytes"
# right sides at or under this row count use the broadcast join strategy
# (default: ops/join.py MAX_BROADCAST_ROWS). Conf-driven so deployments
# can trade replication memory against exchange latency per mesh.
FUGUE_TPU_CONF_JOIN_BROADCAST_MAX_ROWS = "fugue.tpu.join.broadcast_max_rows"
# --- pipelined out-of-core exchange (docs/shuffle.md "Pipelined
# exchange") --- kill-switch for the overlapped spill pipeline:
# write-behind bucket writes, the memory-resident bucket tier, and
# bucket-pair prefetch + budget-bounded pair grouping in the spill join.
# =false restores the strict phase-barrier PR 8 path bit-identically
# (identical span multisets, identical per-bucket chunking).
FUGUE_TPU_CONF_SHUFFLE_PIPELINE_ENABLED = "fugue.tpu.shuffle.pipeline.enabled"
# host-byte ledger for the memory-resident bucket tier: buckets whose
# accumulated arrow bytes fit this budget never touch disk (demoted
# largest-first under pressure; demoted buckets keep the full
# write+publish+recovery discipline). 0/unset = auto (1/16 of host
# MemTotal, capped at 256MiB); negative disables the tier.
FUGUE_TPU_CONF_SHUFFLE_MEM_BUCKET_BYTES = "fugue.tpu.shuffle.mem_bucket_bytes"
# bucket-pair prefetch depth for the spill join's consumer: the producer
# reads+decodes+pads+device-ingests pair group i+1 while the kernel runs
# group i. unset = the stream prefetcher's auto default (0 on single-core
# cpu-mesh hosts, where a producer thread only steals consumer time);
# <=0 = consume serially (still grouped + mem-tiered when the pipeline
# is enabled).
FUGUE_TPU_CONF_SHUFFLE_PREFETCH_DEPTH = "fugue.tpu.shuffle.prefetch_depth"
# bounded write-behind queue depth (bucket batches in flight to the
# background spill writer thread before the partitioner blocks)
FUGUE_TPU_CONF_SHUFFLE_WRITEBEHIND_DEPTH = "fugue.tpu.shuffle.writebehind_depth"
# --- device-resident exchange (docs/shuffle.md "Device exchange") ---
# kill-switch for the device_exchange strategy rung: joins whose sides
# exceed the per-device budget but fit aggregate mesh memory exchange
# rows on-device with a staged one-hop-at-a-time collective schedule
# instead of spilling. =false restores the three-rung ladder (such
# joins spill, bit-identically to the pre-exchange behavior).
FUGUE_TPU_CONF_SHUFFLE_DEVICE_EXCHANGE_ENABLED = (
    "fugue.tpu.shuffle.device_exchange.enabled"
)
# per-stage collective payload cap for the staged exchange schedule, in
# bytes per device. 0/unset = auto (1/8 of device_budget_bytes — small
# enough that a stage buffer never threatens the budget, large enough
# that per-stage fixed costs amortize across the schedule).
FUGUE_TPU_CONF_SHUFFLE_EXCHANGE_STAGE_BYTES = (
    "fugue.tpu.shuffle.device_exchange.stage_bytes"
)

# --- multi-tenant serving layer (fugue_tpu/serve, docs/serving.md) ---
# concurrent workflow executions one EngineServer runs at a time (its
# worker-thread pool size); everything past it waits in the admission queue
FUGUE_TPU_CONF_SERVE_MAX_CONCURRENT = "fugue.tpu.serve.max_concurrent"
# admission queue capacity: submissions past it are REJECTED (the /readyz
# readiness endpoint reports "overloaded" with a 503 before that happens,
# so a load balancer can shed first)
FUGUE_TPU_CONF_SERVE_QUEUE_DEPTH = "fugue.tpu.serve.queue_depth"
# priority for submissions that don't name one (lower = sooner; ties FIFO)
FUGUE_TPU_CONF_SERVE_DEFAULT_PRIORITY = "fugue.tpu.serve.default_priority"
# starvation guard: a queued execution's effective priority improves by
# one level per aging_s seconds waited, so FIFO-within-priority can never
# starve the lowest level under a steady high-priority stream. 0 disables.
FUGUE_TPU_CONF_SERVE_AGING_S = "fugue.tpu.serve.aging_s"
# bytes charged against a tenant's budget per admitted submission when
# the submission doesn't declare its own reserve_bytes (replaced by the
# measured result bytes once the run finishes — live accounting)
FUGUE_TPU_CONF_SERVE_RESERVE_BYTES = "fugue.tpu.serve.reserve_bytes"
# how many completed submissions the server retains for result pickup
# (oldest evicted past it; their tenant byte charge releases on eviction)
FUGUE_TPU_CONF_SERVE_RETAIN = "fugue.tpu.serve.retain"
# per-tenant overlays: fugue.tpu.serve.tenant.<id>.priority (scheduling
# default), fugue.tpu.serve.tenant.<id>.budget_bytes (admission gate:
# live charged bytes + the new reserve must stay under it; 0 = unlimited),
# and fugue.tpu.serve.tenant.<id>.conf.<key> (per-run conf overlay — any
# fugue.tpu.* key: workflow.run scopes conf per run, so an overlay can
# never leak into another tenant's run; non-fugue.tpu keys are dropped
# with a warning)
FUGUE_TPU_CONF_SERVE_TENANT_PREFIX = "fugue.tpu.serve.tenant."
# keys every tenant conf overlay must start with (run-scoped by the
# workflow.run conf overlay; see docs/serving.md)
FUGUE_TPU_CONF_SERVE_TENANT_OVERLAY_PREFIX = "fugue.tpu."
# distinct tenant ids the serving layer keeps state for (per-tenant stats
# breakdown, parsed tenant policies, the one-warning-per-tenant set) —
# least-recently-seen tenants past it are evicted, the same LRU
# discipline as the serve.retain retention ring: a hostile client minting
# tenant ids must not leak memory in a long-lived server
FUGUE_TPU_CONF_SERVE_MAX_TENANTS = "fugue.tpu.serve.max_tenants"

# --- serving fleet (fugue_tpu/serve/fleet.py, docs/serving.md "Fleet") ---
# master switch for cross-replica coordination. ON by default but only
# ACTIVE when the engine mounts a shared disk store (fugue.tpu.cache.dir)
# — replicas sharing that directory collapse identical submissions across
# processes via claim files and serve each other's published results.
# =false (or a single replica with no shared store) preserves the
# single-server behavior bit-identically, including the /serve/* wire
# contract.
FUGUE_TPU_CONF_SERVE_FLEET_ENABLED = "fugue.tpu.serve.fleet.enabled"
# claim lease in seconds: a claim older than this whose owner can't be
# proven alive is STEALABLE — a dead replica's in-flight plan is taken
# over by whichever waiter gets the atomic claim rewrite in first. A
# same-host owner with a dead pid is stealable immediately.
FUGUE_TPU_CONF_SERVE_FLEET_LEASE_S = "fugue.tpu.serve.fleet.lease_s"
# how often a cross-replica waiter re-checks the shared store for the
# owner's published result (and the owner's claim for expiry)
FUGUE_TPU_CONF_SERVE_FLEET_POLL_S = "fugue.tpu.serve.fleet.poll_s"
# published serve-result payloads kept in the shared store (mtime-LRU
# eviction past it, the ArtifactStore discipline)
FUGUE_TPU_CONF_SERVE_FLEET_MAX_RESULTS = "fugue.tpu.serve.fleet.max_results"
# this replica's stable identity in claim files / journal names /
# /readyz; default "<hostname>-<pid>" (unique per process)
FUGUE_TPU_CONF_SERVE_REPLICA_ID = "fugue.tpu.serve.replica_id"
# crash-safe submission journal: the directory holding each replica's
# append-only fsync'd WAL (<replica_id>.jsonl). Unset (default) disables
# journaling; on restart a replica REPLAYS its own unfinished entries
# under their original idempotency keys (docs/serving.md "Fleet").
FUGUE_TPU_CONF_SERVE_JOURNAL_DIR = "fugue.tpu.serve.journal.dir"
# journal compaction threshold (bytes): past it the WAL is rewritten
# atomically with every terminal submission's records dropped — replay
# semantics are provably unchanged (unfinished() parity). 0 disables.
FUGUE_TPU_CONF_SERVE_JOURNAL_MAX_BYTES = "fugue.tpu.serve.journal.max_bytes"

# --- continuous views (fugue_tpu/views, docs/views.md) ---
# master kill-switch, default OFF: =false means no registration
# endpoints (they 404), no watcher threads, and a serve wire contract /
# span multiset bit-identical to the pre-views tiers. Turning it on
# requires a shared store (fugue.tpu.cache.dir) — the registry, heads,
# leases and generation payloads all live there so any replica can serve
# while exactly one maintains.
FUGUE_TPU_CONF_VIEWS_ENABLED = "fugue.tpu.views.enabled"
# watcher loop interval in seconds: how often the maintainer re-observes
# every watched source (and renews its watch leases)
FUGUE_TPU_CONF_VIEWS_POLL_S = "fugue.tpu.views.poll_s"
# per-view watch lease duration: a lease this old whose holder cannot be
# proven alive (dist heartbeat / same-host pid probe) is stealable — the
# exactly-one-maintainer guarantee under replica death
FUGUE_TPU_CONF_VIEWS_LEASE_S = "fugue.tpu.views.lease_s"
# published generations retained per view beyond the pinned latest one
# (older generation payloads are deleted by the maintainer on publish)
FUGUE_TPU_CONF_VIEWS_KEEP_GENERATIONS = "fugue.tpu.views.keep_generations"
# how many priority points an SLO-at-risk refresh gains (priority is
# min-wins, so the boost SUBTRACTS; floor 0)
FUGUE_TPU_CONF_VIEWS_SLO_BOOST = "fugue.tpu.views.slo_boost"
# fraction of a tenant's freshness_s after which a pending refresh counts
# as at-risk and takes the boost (breach itself is at 1.0)
FUGUE_TPU_CONF_VIEWS_SLO_RISK_FRACTION = "fugue.tpu.views.slo_risk_fraction"
# registered views cap (bounds /metrics cardinality and registry scans)
FUGUE_TPU_CONF_VIEWS_MAX = "fugue.tpu.views.max"
# how long a maintainer waits for one refresh submission to finish before
# counting it failed and retrying next tick
FUGUE_TPU_CONF_VIEWS_REFRESH_TIMEOUT_S = "fugue.tpu.views.refresh_timeout_s"

# --- multi-host worker tier (fugue_tpu/dist, docs/distributed.md) ---
# master kill-switch: =false makes DistSupervisor.run_* execute the whole
# job serially in THIS process (same functions, same bucket order) —
# bit-identical to the distributed result by construction.
FUGUE_TPU_CONF_DIST_ENABLED = "fugue.tpu.dist.enabled"
# task lease duration: a lease this old whose owner cannot be proven
# alive (heartbeat) is stealable by any live worker; owners renew at
# lease_s/3 while executing, so only a dead or wedged owner expires.
FUGUE_TPU_CONF_DIST_LEASE_S = "fugue.tpu.dist.lease_s"
# heartbeat protocol: every worker/replica writes
# <heartbeat.dir>/<id>.hb.json every interval_s (atomic rename); a
# heartbeat older than stale_after_s is PROOF of death for lease/claim
# stealing — the cross-host replacement for same-host pid probes. The
# dir is shared by the dist worker tier AND the serve fleet (an
# EngineServer with this key set writes heartbeats under its replica_id,
# and claim stealing in cache/store.py consults them).
FUGUE_TPU_CONF_DIST_HB_DIR = "fugue.tpu.dist.heartbeat.dir"
FUGUE_TPU_CONF_DIST_HB_INTERVAL_S = "fugue.tpu.dist.heartbeat.interval_s"
FUGUE_TPU_CONF_DIST_HB_STALE_S = "fugue.tpu.dist.heartbeat.stale_after_s"
# shuffle-fragment fetch mode: "auto" reads the producer's file directly
# when its path is visible on this host's filesystem and falls back to
# the producer's HTTP /dist/fetch route; "remote" always fetches over
# HTTP except from this worker's own dir (the true multi-host shape —
# what the chaos gate runs); "local" never fetches (single-host tier).
FUGUE_TPU_CONF_DIST_FETCH = "fugue.tpu.dist.fetch"
# reduce-side bucket count for the network-partitioned exchange
FUGUE_TPU_CONF_DIST_BUCKETS = "fugue.tpu.dist.buckets"
# straggler mitigation: a task leased (and renewed) by a LIVE owner for
# longer than this is marked speculative — a second worker re-executes
# it and the first published done-record wins (artifacts are content-
# addressed, so the loser's publish dedups). 0 (default) disables.
FUGUE_TPU_CONF_DIST_SPECULATIVE_AFTER_S = "fugue.tpu.dist.speculative_after_s"
# supervisor/worker poll cadence over the shared board
FUGUE_TPU_CONF_DIST_POLL_S = "fugue.tpu.dist.poll_s"
# reduce-side fragment prefetch depth: fragments for a bucket are fetched
# (local read or remote /dist/fetch) through a depth-bounded background
# producer so network/disk fetch of fragment i+1 overlaps the decode and
# reduce compute of fragment i. <=0 = fetch serially (the pre-pipeline
# shape); default 2 (network fetch releases the GIL, so the overlap is
# real even on single-core hosts).
FUGUE_TPU_CONF_DIST_FETCH_PREFETCH_DEPTH = "fugue.tpu.dist.fetch_prefetch_depth"
# shared task-board root for DISTRIBUTED WORKFLOW execution: when set (and
# dist.enabled is true), workflow.run hands distributable fragments of the
# post-optimization DAG — Load roots + row-local chains into an equi-join,
# keyed aggregate, or bucket-local SQL SELECT — to
# DistSupervisor.run_workflow_job as leased board tasks
# (fugue_tpu/plan/distribute.py, docs/distributed.md "Distributed
# workflows"). Unset (default) = planner inert, fully local execution.
FUGUE_TPU_CONF_DIST_BOARD = "fugue.tpu.dist.board"
# wall-clock timeout (seconds) for one distributed workflow fragment's
# board job; 0/unset = unbounded (recovery is driven by leases, not this)
FUGUE_TPU_CONF_DIST_WORKFLOW_TIMEOUT_S = "fugue.tpu.dist.workflow_timeout_s"
# wall-clock deadline (seconds) across ALL RetryPolicy-driven attempts of
# one /dist/fetch fragment fetch (conf prefix fugue.tpu.retry.dist.*);
# past it the fetch stops retrying and the orphaned-fragment ladder runs
FUGUE_TPU_CONF_RETRY_DIST_DEADLINE_S = "fugue.tpu.retry.dist.deadline_s"

# --- cost-based adaptive execution (fugue_tpu/tuning, docs/tuning.md) ---
# Feedback layer that re-derives stream chunk size / prefetch depth and
# shuffle bucket sizing from the engine's OWN telemetry (pipeline stats,
# spill-join observations), keyed by plan fingerprint. Master kill-switch:
# =false restores the static-conf behavior bit-identically (no store
# reads, no writes, every knob resolves exactly as before this layer).
# Per-workflow/compile-conf scoped like fugue.tpu.plan.* — workflow.run
# never writes fugue.tpu.tuning.* into a shared engine's conf.
FUGUE_TPU_CONF_TUNING_ENABLED = "fugue.tpu.tuning.enabled"
FUGUE_TPU_CONF_TUNING_PREFIX = "fugue.tpu.tuning."
# where learned settings persist (atomic temp-write+rename; corrupt or
# unwritable files degrade to defaults with ONE warning). Default: the
# package's ops/_tuned.json, next to the dense-sum A/B winner; the
# FUGUE_TPU_TUNING_PATH env var overrides (test isolation).
FUGUE_TPU_CONF_TUNING_PATH = "fugue.tpu.tuning.path"
# plan-fingerprint entries kept in the store; least-recently-used past it
# are evicted at publish time (stale-plan hygiene for long-lived servers)
FUGUE_TPU_CONF_TUNING_MAX_ENTRIES = "fugue.tpu.tuning.max_entries"

FUGUE_COMPILE_TIME_CONFIGS = {
    FUGUE_CONF_WORKFLOW_AUTO_PERSIST,
    FUGUE_CONF_WORKFLOW_AUTO_PERSIST_VALUE,
    FUGUE_CONF_SQL_IGNORE_CASE,
    FUGUE_CONF_SQL_DIALECT,
}

_FUGUE_GLOBAL_CONF = ParamDict(
    {
        FUGUE_CONF_WORKFLOW_CONCURRENCY: 1,
        FUGUE_CONF_WORKFLOW_AUTO_PERSIST: False,
        FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE: "fugue.,fugue_tpu.,concurrent.,"
        "pandas.,pyarrow.,jax.,numpy.",
        FUGUE_CONF_WORKFLOW_EXCEPTION_INJECT: 3,
        FUGUE_CONF_WORKFLOW_EXCEPTION_OPTIMIZE: True,
        FUGUE_CONF_SQL_IGNORE_CASE: False,
        FUGUE_CONF_SQL_DIALECT: "spark",
        FUGUE_CONF_DEFAULT_PARTITIONS: -1,
    }
)


def register_global_conf(conf: Dict[str, Any], on_dup: int = ParamDict.OVERWRITE) -> None:
    """Merge keys into the process-level global conf (lowest priority layer)."""
    _FUGUE_GLOBAL_CONF.update(conf, on_dup=on_dup)
