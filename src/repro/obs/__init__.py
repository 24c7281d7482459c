"""Unified observability for the serving stack.

Three parts, all dependency-free (stdlib + the repo itself):

* :mod:`repro.obs.trace` — head-sampled cross-process request tracing:
  :class:`Tracer` / :class:`TraceRecorder` / :func:`assemble`. Trace
  contexts ride the replicated tier's wire format, so one sampled
  request reconstructs a single client -> router -> replica -> forward
  span tree. :func:`annotate` marks the program's layers on the
  profiler's clock while a profile is recorded (JAX's profiler, then).
* :mod:`repro.obs.registry` — one typed metrics registry
  (:class:`MetricsRegistry`) with adapters over every existing
  telemetry source (server metrics, service phase/cache stats, router
  health, shared-cache occupancy, drift gauges), snapshotting to one
  versioned schema.
* :mod:`repro.obs.drift` — :class:`DriftMonitor`, the online accuracy
  sentinel: sampled served predictions scored against the analyzer
  oracle in the background, rolling per-target Spearman/MAE plus
  OOV/unk hysteresis alarms.

Egress lives in :mod:`repro.obs.export` (periodic JSONL stream +
opt-in Prometheus text endpoint); ``launch/obs.py`` is the CLI over
the stream. See ``docs/observability.md``.
"""
import importlib

# Names resolve on first use (PEP 562), so that ``repro.core`` can import
# ``repro.obs.trace`` alone without loading the exporters, the registry
# and the drift monitor.
_HOME = {
    "Alarm": "drift", "DriftMonitor": "drift",
    "JsonlExporter": "export", "PromExporter": "export",
    "to_prometheus": "export",
    "MetricsRegistry": "registry", "register_drift": "registry",
    "register_router": "registry", "register_server": "registry",
    "register_service": "registry", "register_shared_cache": "registry",
    "register_supervisor": "registry", "register_tracer": "registry",
    "Span": "trace", "TraceContext": "trace", "TraceRecorder": "trace",
    "Tracer": "trace", "TraceTree": "trace", "annotate": "trace",
    "assemble": "trace", "completeness": "trace",
}


def __getattr__(name):
    if name not in _HOME:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    return getattr(importlib.import_module(f"repro.obs.{_HOME[name]}"),
                   name)


__all__ = [
    "Alarm", "DriftMonitor", "JsonlExporter", "MetricsRegistry",
    "PromExporter", "Span", "TraceContext", "TraceRecorder", "Tracer",
    "TraceTree", "annotate", "assemble", "completeness", "register_drift",
    "register_router", "register_server", "register_service",
    "register_shared_cache", "register_supervisor", "register_tracer",
    "to_prometheus",
]
