"""Observability: pipeline tracing + metrics for the serving stack.

One facade object (:class:`Obs`) bundles the two backbones every layer
shares:

* ``obs.tracer`` — span tracer exporting Chrome trace-event JSON and,
  optionally, profiler annotations (:mod:`repro.obs.trace`).
* ``obs.metrics`` — labeled Counter/Gauge/Histogram registry with JSON
  snapshot and Prometheus text exposition (:mod:`repro.obs.metrics`).

Components take ``obs=None`` and fall back to :data:`NULL_OBS`, whose
tracer and registry are shared no-op singletons — the disabled mode is
allocation-free and adds nothing to the engine loop (tested in
``tests/test_obs.py``).  Build a live one with :func:`make_obs`.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.obs.metrics import (NULL_REGISTRY, NullRegistry,  # noqa: F401
                               Registry, acceptance_buckets)
from repro.obs.request_trace import (NULL_REQUEST_TRACKER,  # noqa: F401
                                     NullRequestTracker, RequestTracker,
                                     timelines_summary)
from repro.obs.slo import (SLO, FlightRecorder, SLOMonitor,  # noqa: F401
                           as_slos)
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer  # noqa: F401


@dataclass(frozen=True)
class Obs:
    """Tracer + metrics registry bundle passed down the serving stack."""
    tracer: Tracer | NullTracer
    metrics: Registry | NullRegistry

    @property
    def enabled(self) -> bool:
        """True when either backbone records anything."""
        return self.tracer.enabled or self.metrics.enabled


NULL_OBS = Obs(NULL_TRACER, NULL_REGISTRY)


def make_obs(trace: bool = False, metrics: bool = True,
             fence: bool = True, annotations: bool = False,
             virtual_clock=None) -> Obs:
    """Build an :class:`Obs`; disabled backbones are the null singletons.

    ``fence`` makes device-phase spans ``jax.block_until_ready`` their
    results for honest timing (slightly serializes dispatch — that is
    the point); ``annotations`` additionally enters
    ``jax.profiler.TraceAnnotation`` per span so phase names appear in
    XLA profiler dumps.
    """
    if not (trace or metrics):
        return NULL_OBS
    tr = Tracer(fence=fence, annotations=annotations,
                virtual_clock=virtual_clock) if trace else NULL_TRACER
    reg = Registry() if metrics else NULL_REGISTRY
    return Obs(tr, reg)
