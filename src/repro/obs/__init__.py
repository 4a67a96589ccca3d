"""Observability layer: tracing, metrics, and the energy ledger.

* :mod:`repro.obs.schema` — the versioned event schema + validator +
  converters for the legacy event streams;
* :mod:`repro.obs.tracer` — :class:`Tracer` (modeled-time recorder
  emitting Chrome ``trace_event`` JSON) and the no-op
  :class:`NullTracer`;
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` with counters /
  gauges / histograms (p50/p99);
* :mod:`repro.obs.ledger` — the three-tier energy-conservation ledger
  and the ``check_*`` reconciliation functions;
* :mod:`repro.obs.scopes` — the vocabulary of ``jax.named_scope`` names
  on the training path (:data:`SCOPES`, and :data:`MOE_SCOPES` inside an
  expert layer) and :func:`scope`.

Imports only :mod:`repro.core` (+ stdlib / numpy / jax), so every other
subpackage may depend on it without cycles.
"""
from .ledger import (EnergyLedger, check_executor, check_fleet,
                     check_replica, executor_ledger, fleet_ledger,
                     replica_ledger, segment_breakdown)
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .schema import (CATEGORIES, KINDS, OBS_SCHEMA_VERSION,
                     from_controller_events, from_governor_events,
                     from_recovery_books, from_replica_events,
                     ingest_legacy_streams, make_event,
                     validate_trace_dict)
from .scopes import MOE_SCOPES, SCOPES, scope
from .tracer import NULL_TRACER, NullTracer, Tracer

__all__ = [
    "OBS_SCHEMA_VERSION", "KINDS", "CATEGORIES", "make_event",
    "validate_trace_dict", "from_governor_events",
    "from_controller_events", "from_replica_events",
    "from_recovery_books", "ingest_legacy_streams",
    "Tracer", "NullTracer", "NULL_TRACER",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "EnergyLedger", "segment_breakdown", "executor_ledger",
    "replica_ledger", "fleet_ledger", "check_executor",
    "check_replica", "check_fleet", "SCOPES", "MOE_SCOPES", "scope",
]
