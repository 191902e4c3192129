"""One observability plane: request tracing, the step timeline and
device scopes, typed events, fleet metrics aggregation, training
telemetry, on-demand profiling.

See docs/OBSERVABILITY.md for the schemas, the endpoint map, and the
overhead budget.  Everything here is host-side and dependency-free:
tracing and events do no device work, so they can never change an XLA
cache key or add a compile (the same contract as
``resilience/faults.py`` unarmed); ``jax.profiler.TraceAnnotation`` and
``jax.named_scope`` are taken from a ``jax`` that is loaded already.
"""

from perceiver_tpu.obs.events import (
    SCHEMA,
    EventLog,
    default_log,
    emit,
    set_default_log,
    validate_event,
)
from perceiver_tpu.obs.trace import (
    DEVICE_SCOPES,
    ENCLOSING_SPANS,
    PHASES,
    PROCESS_PHASES,
    TILED_PHASES,
    TRAIN_PHASES,
    SpanCollector,
    Timeline,
    TraceBuffer,
    TraceContext,
    attach,
    attached,
    default_buffer,
    device_scope,
    enabled,
    from_wire,
    region,
    set_default_buffer,
    set_enabled,
    set_timeline,
    span,
    start_trace,
    timeline,
)

__all__ = [
    "DEVICE_SCOPES",
    "ENCLOSING_SPANS",
    "PHASES",
    "PROCESS_PHASES",
    "SCHEMA",
    "TILED_PHASES",
    "TRAIN_PHASES",
    "EventLog",
    "SpanCollector",
    "Timeline",
    "TraceBuffer",
    "TraceContext",
    "attach",
    "attached",
    "default_buffer",
    "default_log",
    "device_scope",
    "emit",
    "enabled",
    "from_wire",
    "region",
    "set_default_buffer",
    "set_default_log",
    "set_enabled",
    "set_timeline",
    "span",
    "start_trace",
    "timeline",
    "validate_event",
]
