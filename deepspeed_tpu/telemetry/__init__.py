"""Unified telemetry layer (metrics registry + trace timeline +
fleet federation + SLO accounting + FLOPs/MFU profiling).

Coordinated pieces (design notes in each module):

 - :mod:`~deepspeed_tpu.telemetry.metrics` — counters / gauges /
   fixed-bucket streaming histograms with labels; Prometheus text
   exposition, JSON snapshots, and ``(name, value, step)`` events for
   the ``monitor/`` backends.  ``ServingEngine.stats()`` and the training
   engine's monitor events are views over one registry each.
 - :mod:`~deepspeed_tpu.telemetry.trace` — a bounded ring buffer of
   per-request scheduler events exportable as Chrome ``trace_event``
   JSON (Perfetto) with cross-lane flow events; every span doubles as
   a ``jax.profiler`` annotation (``ds.<role>.<name>``), plus the
   ``jax.profiler`` window bracket.
 - :mod:`~deepspeed_tpu.telemetry.idle_gaps` — from one profile, the
   device's idle time partitioned over the program's ``ds.*`` spans
   (``python -m deepspeed_tpu.telemetry.idle_gaps <profile_dir>``).
 - :mod:`~deepspeed_tpu.telemetry.scopes` /
   :mod:`~deepspeed_tpu.telemetry.programs` /
   :mod:`~deepspeed_tpu.telemetry.hlo_text` /
   :mod:`~deepspeed_tpu.telemetry.device_scopes` — the device's BUSY
   time by layer: the one vocabulary of ``jax.named_scope`` names, what
   each engine keeps at a program's first call to find its executable
   again, the package's one reader of compiled HLO text (a scope table a
   program, built on demand), and the reader that sums a profile's device
   seconds by scope (``python -m deepspeed_tpu.telemetry.device_scopes
   <profile_dir> --tables tables.json``; it shares
   :mod:`~deepspeed_tpu.telemetry.profile`, the one profile loader, with
   ``idle_gaps``).
 - :mod:`~deepspeed_tpu.telemetry.aggregate` — fleet federation: merge
   the router + replica registries into one ``replica=``-labeled
   registry (bucket-wise-summed histograms) and the per-replica trace
   rings into one multi-``pid`` Chrome document.
 - :mod:`~deepspeed_tpu.telemetry.server` — the live exposition hop: a
   thread-owned stdlib HTTP server for ``/metrics`` (Prometheus text),
   ``/stats`` (JSON), and ``/trace`` (merged Chrome trace).
 - :mod:`~deepspeed_tpu.telemetry.slo` — per-``slo_class`` TTFT/TPOT
   histograms, attainment counters against configurable targets, and
   burn-rate gauges behind ``slo_report()``; ``merged_windowed_burn``
   reports burn over a rolling window (the autoscaling / incident
   signal).
 - :mod:`~deepspeed_tpu.telemetry.flops` — the serving FLOPs/MFU
   profiler: XLA ``cost_analysis`` per compiled program family (analytic
   fallback), ``serving_model_flops_total``, the MFU gauge, and the
   busy-fraction breakdown.
 - :mod:`~deepspeed_tpu.telemetry.incident` — the black-box flight
   recorder: trigger-driven atomic incident bundles
   (:class:`IncidentRecorder`), the no-progress
   :class:`StallWatchdog`, and ``replay_bundle`` / ``bin/graft-replay``
   deterministic re-execution.

See ``docs/observability.md`` for the metric name table, label
conventions, the fleet-endpoint walkthrough, and the overhead contract.
"""

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DEFAULT_TIME_BUCKETS_S)
from .trace import ProfilerWindow, TraceTimeline, validate_chrome_trace
from .aggregate import federate, merge_chrome_traces, merge_histograms
from .server import MetricsServer
from .slo import (DEFAULT_SLO_TARGETS, SLOTracker, merged_slo_report,
                  merged_windowed_burn)
from .incident import (IncidentRecorder, StallWatchdog, is_bundle,
                       load_bundle, replay_bundle)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_TIME_BUCKETS_S", "ProfilerWindow", "TraceTimeline",
    "validate_chrome_trace", "federate", "merge_chrome_traces",
    "merge_histograms", "MetricsServer", "DEFAULT_SLO_TARGETS",
    "SLOTracker", "merged_slo_report", "merged_windowed_burn",
    "IncidentRecorder", "StallWatchdog", "is_bundle", "load_bundle",
    "replay_bundle",
]
