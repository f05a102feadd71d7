"""Phase tracing: device scopes, host spans on the profiler's clock,
a compile counter, Perfetto profiles.

* **Device phases** — the engine's programs are annotated with
  ``jax.named_scope`` scopes (``kpynq/seed``, ``kpynq/group``,
  ``kpynq/init``, ``kpynq/candidate_pass``, ``kpynq/move_and_bounds``
  with ``kpynq/reduce`` and ``kpynq/refresh``, ``kpynq/inertia``), so
  any profiler view of the compiled programs attributes device time to
  engine phases instead of a wall of fused HLO. :func:`profile` wraps a
  callable in ``jax.profiler.trace`` and returns the directory holding
  the Perfetto trace (open at https://ui.perfetto.dev, or feed to
  TensorBoard's profile plugin).
* **Host spans** — :func:`span` opens a ``jax.profiler.TraceAnnotation``,
  so while a profiler records, the span lands in the same trace as the
  device ops and on their clock (``KMeans.fit`` opens ``kpynq.fit``,
  ``kpynq.seed``, ``kpynq.fetch``; ``engine.fit`` opens
  ``kpynq.tables``, ``kpynq.loop``, ``kpynq.epilogue``). Given a
  registry, it also times the region into that registry's histogram
  and event log (``tune.autotune`` around each measured candidate).
* **Compiles** — :func:`compile_count` counts the programs this process
  has lowered, each an in-memory jit-cache miss that costs a compile or
  a persistent-cache load; ``KMeans.fit`` records its own count as
  ``EngineStats.compiles``.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
import threading
import time

import jax

from .metrics import MetricsRegistry, default_registry

# span-duration histogram buckets: micro-benchmarks to multi-minute fits
SPAN_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                30.0, 60.0, 300.0)


@contextlib.contextmanager
def span(name: str, registry: MetricsRegistry | None = None, **fields):
    """A named host region.

    Opens a ``jax.profiler.TraceAnnotation(name)``: while a profiler
    records, the span lands in the trace's host plane on the device
    ops' clock, so the device's idle time can be put down to it; with
    no profiler it costs about a microsecond. Given a ``registry``, the
    duration also goes into its ``span_seconds`` histogram (labelled by
    span name) and a ``span`` event (with any extra ``fields``); with
    none, nothing is recorded beyond the annotation. Yields a dict the
    caller may add result fields to; they land in the same event.

        with obs.span("tune.measure", registry=reg, backend="compact") as s:
            t = measure(cfg)
            s["seconds_measured"] = t
    """
    extra: dict = {}
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield extra
        finally:
            if registry is not None:
                dt = time.perf_counter() - t0
                registry.histogram("span_seconds", "host span durations",
                                   labels={"span": name},
                                   buckets=SPAN_BUCKETS).observe(dt)
                # span's own keys win over caller fields (never a TypeError)
                merged = {**fields, **extra, "name": name, "seconds": dt}
                registry.log_event("span", **merged)


# one jax.monitoring event per program lowered to MLIR: a jit-cache miss
# in memory, which a backend compile or a persistent-cache load follows
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowered = 0
_lowered_lock = threading.Lock()
_listening = False


def _on_event_duration(event: str, duration: float, **_) -> None:
    global _lowered
    if event == LOWERING_EVENT:
        with _lowered_lock:
            _lowered += 1


def compile_count() -> int:
    """Programs this process has lowered since the first call.

    Monotonic; the difference of two readings is the compiles (or
    persistent-cache loads) between them. The first call registers one
    process-wide ``jax.monitoring`` listener, so programs lowered
    before it are not counted."""
    global _listening
    with _lowered_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _listening = True
        return _lowered


def profile(fn, *args, trace_dir: str | None = None,
            registry: MetricsRegistry | None = None, **kwargs):
    """Run ``fn(*args, **kwargs)`` under ``jax.profiler.trace`` and
    block on its output, so the trace covers the real device work.

    Returns ``(result, trace_dir)``; the directory contains a
    Perfetto-compatible trace (``plugins/profile/<run>/*.trace.json.gz``)
    whose device timeline carries the engine's ``kpynq/*`` named-scope
    phase annotations. ``trace_dir=None`` creates one under the system
    temp dir. Also logged as a ``profile`` event in the registry so the
    export names the artifact path.
    """
    if trace_dir is None:
        trace_dir = tempfile.mkdtemp(prefix="kpynq_trace_")
    os.makedirs(trace_dir, exist_ok=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(str(trace_dir)):
        out = fn(*args, **kwargs)
        jax.block_until_ready(jax.tree.leaves(out))
    dt = time.perf_counter() - t0
    (registry or default_registry()).log_event(
        "profile", trace_dir=str(trace_dir), seconds=dt,
        fn=getattr(fn, "__name__", repr(fn)))
    return out, str(trace_dir)
