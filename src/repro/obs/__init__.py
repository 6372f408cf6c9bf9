"""Precision-health observability: counters, metrics pipeline, spans,
anomaly detectors.

The subsystem has four layers (see docs/metrics_schema.md):
 * counters  — per-site FP8 saturation / flush fractions computed from the
   same payload bit patterns the delayed-scaling epilogues already read
   (zero extra HBM passes; kernel paths count in VMEM next to amax).
 * metrics   — typed MetricsLogger: versioned-schema jsonl sink with
   scalar/vector-aware serialization and rolling-window aggregation.
 * trace     — one measurement system: device scopes of the compiled step
   (`SCOPES`, mapped to trace ops by `op_scopes`), host phase spans on the
   profiler's clock (`Tracer`), and compile counters.
 * health    — anomaly detectors over the metrics stream (loss-scale
   flapping, saturation, stuck/NaN amax, straggler streaks), surfaced as
   structured `health_events` records.

Law: enabling the counters changes no computed bits — the telemetry rides
next to the training math, never inside it (parity-locked in
tests/test_obs.py).
"""
from repro.obs.counters import (counts_to_frac, payload_health,
                                payload_thresholds, value_counts)
from repro.obs.health import HealthConfig, HealthMonitor
from repro.obs.metrics import SCHEMA_VERSION, MetricsLogger
from repro.obs.trace import Tracer

__all__ = [
    "counts_to_frac", "payload_health", "payload_thresholds", "value_counts",
    "HealthConfig", "HealthMonitor",
    "SCHEMA_VERSION", "MetricsLogger",
    "Tracer",
]
