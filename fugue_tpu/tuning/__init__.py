"""Cost-based adaptive execution (docs/tuning.md): a feedback layer that
re-derives the engine's performance knobs — stream chunk size, prefetch
depth, shuffle bucket count, join-side size estimates — from its own
telemetry, keyed by plan fingerprint and persisted to ``ops/_tuned.json``
so a warm server converges across submissions and survives restart.
``fugue.tpu.tuning.enabled=false`` restores the static-conf engine
bit-identically."""

from .stats import TuningStats
from .store import TunedStore, default_tuned_path, resolve_tuned_path
from .tuner import (
    ExchangeHandle,
    StreamHandle,
    Tuner,
    adjust_buckets,
    adjust_stream,
    current_scope,
    describe_tuning,
    plan_fingerprint,
    run_scope,
    tuning_enabled,
)

__all__ = [
    "ExchangeHandle",
    "StreamHandle",
    "TunedStore",
    "Tuner",
    "TuningStats",
    "adjust_buckets",
    "adjust_stream",
    "current_scope",
    "default_tuned_path",
    "describe_tuning",
    "plan_fingerprint",
    "resolve_tuned_path",
    "run_scope",
    "tuning_enabled",
]
