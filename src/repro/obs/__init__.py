"""Unified observability layer: metrics, traces, flight recorder, logs.

One telemetry spine for the whole serving stack (see ARCHITECTURE.md
"Observability"):

  * ``metrics`` — thread-safe counters/gauges/bounded-p50-p99 histograms
    in ``MetricsRegistry`` instances; ``REGISTRY`` is the process-wide
    default, with JSON and Prometheus text exposition.
  * ``trace`` — request/wave trace IDs (minted at ``FrontDoor.submit``)
    and thread-local ``span()`` contexts; ``StageTimer`` times one leaf
    of a coarse stage into the per-stage trace dict, spans and registry,
    and holds a ``jax.profiler.TraceAnnotation`` (``gestore.<stage>.<leaf>``)
    so a profiler trace shows it on the device's clock.
  * ``recorder`` — ``RECORDER``, a bounded ring of structured events
    (rejections, failures, pool churn, spans) dumping to JSON on demand
    or on unhandled failure (``GESTORE_FLIGHT_DUMP``).
  * ``log`` — the leveled, env-configurable (``GESTORE_LOG``) structured
    logger; quiet by default, the only sanctioned output path for
    library code (ruff bans ``print`` under ``src/``).

Importing ``repro.obs`` stays stdlib+numpy light (``jax.profiler`` is
imported on the first ``StageTimer``), so ``core``/``serve`` can depend
on it unconditionally.
"""
from .log import configure as configure_logging, get_logger
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      REGISTRY)
from .recorder import RECORDER, FlightRecorder, install_excepthook
from .trace import (Span, StageTimer, current_span, current_trace_id,
                    new_trace_id, span)

__all__ = [
    "Counter", "FlightRecorder", "Gauge", "Histogram", "MetricsRegistry",
    "RECORDER", "REGISTRY", "Span", "StageTimer", "configure_logging",
    "current_span", "current_trace_id", "get_logger", "install_excepthook",
    "new_trace_id", "snapshot_all", "span",
]


def snapshot_all() -> dict:
    """One combined observability snapshot: global registry metrics and
    the flight-recorder dump — the payload
    ``benchmarks/table10_observability.py`` writes to
    ``BENCH_metrics.json``."""
    return {"metrics": REGISTRY.snapshot(),
            "flight_recorder": RECORDER.dump()}
