"""Structured metrics, tracing & step profiling for every run path.

The observability layer the reference (one ``print`` per 2000 batches) never
had: a per-run :class:`Telemetry` session that the trainer, CLI and bench
harness thread through. Module map:

- ``registry.py`` — :class:`MetricsRegistry`: labeled counters / gauges /
  histograms, JSONL snapshots, Prometheus text exposition;
- ``timer.py`` — :class:`StepTimer`: fenced timing windows with the
  compile-vs-steady split, p50/p95/max step latency, examples/sec and
  tokens/sec (+ opt-in ``jax.stages`` compiled cost stats);
- ``tracing.py`` — the process's span recorder (:func:`tracing.span`,
  :func:`tracing.current`, :class:`Tracer`): the hot paths' host spans on
  ``perf_counter_ns`` in a bounded ring, with JAX's compiles and the
  collector's pauses beside them, exported as Chrome-trace JSON (inspectable
  without XProf; every span also enters ``jax.profiler.TraceAnnotation``,
  so it lies on the XProf timeline when capturing);
- ``memory.py`` — ``jax.live_arrays()`` byte totals + per-device
  ``memory_stats()`` sampling;
- ``ici.py`` — static expected collective bytes/step, read-only reuse of
  ``analysis``'s bytes-over-ICI cost table;
- ``bubble.py`` — the GPipe / 1F1B pipeline-bubble schedule model, plus
  measured-vs-modeled drift helpers (``measured_bubble_fraction``,
  ``bubble_drift``);
- ``session.py`` — :class:`Telemetry`, the orchestrator (``metrics.jsonl``,
  ``trace.json``, ``metrics.prom`` under one directory);
- ``catalog.py`` — the docstring-sourced metric-help catalog behind the
  Prometheus exposition's ``# HELP`` lines (source-parsed via ``ast``, no
  heavy imports);
- ``report.py`` — the stdlib-only run-report CLI: ``python -m
  simple_distributed_machine_learning_tpu.telemetry.report --dir DIR``
  renders per-class attainment, shed breakdown, restart timeline,
  latency quantiles, drift gauges and post-mortem bundles from a
  telemetry directory.

The serving twin lives in ``serve/tracing.py`` (request-scoped async span
timelines on this module's :class:`Tracer` async-event support) and
``serve/flight.py`` (tick flight recorder + post-mortem bundles).

Entry points: ``Trainer(..., telemetry=Telemetry(dir))``, ``cli.py
--telemetry-dir DIR [--telemetry-every N]``, and ``bench.py`` rows (step-time
quantiles + ``bubble_fraction`` ride every result row unconditionally).
"""

from __future__ import annotations

from simple_distributed_machine_learning_tpu.telemetry.bubble import (
    ideal_step_time,
    schedule_bubble_fraction,
)
from simple_distributed_machine_learning_tpu.telemetry.ici import (
    expected_ici_bytes,
)
from simple_distributed_machine_learning_tpu.telemetry.memory import (
    device_memory_stats,
    live_array_bytes,
)
from simple_distributed_machine_learning_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    append_jsonl,
)
from simple_distributed_machine_learning_tpu.telemetry.session import (
    METRICS_FILE,
    PROM_FILE,
    TRACE_FILE,
    Telemetry,
)
from simple_distributed_machine_learning_tpu.telemetry.timer import (
    StepTimer,
    compiled_cost_stats,
)
from simple_distributed_machine_learning_tpu.telemetry.tracing import Tracer

__all__ = [
    "METRICS_FILE", "PROM_FILE", "TRACE_FILE",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "StepTimer",
    "Telemetry", "Tracer", "append_jsonl", "compiled_cost_stats",
    "device_memory_stats", "expected_ici_bytes", "ideal_step_time",
    "live_array_bytes", "schedule_bubble_fraction",
]
