"""repro.obs — observability for the KPynq engine family.

Three layers (see ``docs/observability.md``):

* :mod:`repro.obs.ring` — the device-resident per-iteration telemetry
  ring: layout constants, shard-ring reduction, summaries, the
  live-drain listener registry. The device side lives in
  ``repro.core.engine`` (``EngineCarry.ring``); this module owns the
  host-side semantics.
* :mod:`repro.obs.trace` — phase tracing: ``jax.named_scope`` device
  phases (annotated in the engine), :func:`profile` for Perfetto
  traces, :func:`span` for host spans on the profiler's clock,
  :func:`compile_count` for the programs the process has lowered.
* :mod:`repro.obs.metrics` — the metrics registry
  (counter/gauge/histogram + JSONL event log) with Prometheus-text and
  JSONL exporters, published by all three fit drivers.

This package deliberately imports nothing from ``repro.core`` so the
engine can import it without cycles.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      ObsConfig, default_registry, normalize_obs,
                      reset_default_registry)
from .ring import (N_COUNTERS, RING_COLUMNS, add_ring_listener,
                   caps_from_ring, format_ring_table, reduce_shard_rings,
                   remove_ring_listener, shard_skew, summarize_ring)
from .trace import compile_count, profile, span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "ObsConfig",
    "default_registry", "normalize_obs", "reset_default_registry",
    "N_COUNTERS", "RING_COLUMNS", "add_ring_listener", "caps_from_ring",
    "format_ring_table", "reduce_shard_rings", "remove_ring_listener",
    "shard_skew", "summarize_ring",
    "compile_count", "profile", "span",
]
