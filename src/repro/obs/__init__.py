"""Observability layer: tracing spans + metrics registry + snapshot schema.

Zero-dependency (stdlib only; jax is imported lazily and only for
device-sync timing, device scopes and the profiler), so every layer of the
stack — scheduler, caches, engines, executor, kernels, autotuner — can
import it without cycles or cost. See :mod:`repro.obs.tracing` and
:mod:`repro.obs.metrics` for the two halves, :mod:`repro.obs.validate`
for the snapshot schema contract, and the README "Observability" section
for the operator's view.
"""

from repro.obs import metrics, tracing
from repro.obs.metrics import (MetricsRegistry, counter, gauge, get_registry,
                               histogram, set_registry, snapshot,
                               to_prometheus)
from repro.obs.tracing import (Tracer, configure, device_scope, get_tracer,
                               profile, set_tracer, span, sync_ready)
from repro.obs.validate import validate_snapshot

__all__ = [
    "metrics", "tracing",
    "MetricsRegistry", "counter", "gauge", "histogram", "get_registry",
    "set_registry", "snapshot", "to_prometheus",
    "Tracer", "span", "configure", "get_tracer", "set_tracer", "sync_ready",
    "device_scope", "profile",
    "validate_snapshot",
]
