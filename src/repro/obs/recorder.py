"""The ``Obs`` facade the serving stack threads through (``obs=...``).

One object bundles three sinks — a :class:`~repro.obs.metrics.
MetricsRegistry` (always present; it backs ``scheduler.stats``), an
optional :class:`~repro.obs.trace.Tracer`, and optional profiler
annotations — behind no-op-cheap entry points.  Every hook degrades to a
single attribute test when tracing is off: ``span`` returns the shared
:data:`NULL_SPAN`, ``event``/``counter``/``request_*`` return immediately.
That is the "tracing OFF costs nothing measurable" half of the contract;
the other half (tracing ON moves no tokens and no ``dispatches``/
``host_syncs``) holds because every hook records only host-resident values.

``xla_annotations=True`` makes every serve-loop span (the names of
:data:`repro.obs.scopes.SPANS`) a ``jax.profiler.TraceAnnotation`` as
well, so a concurrently captured XLA profile (``jax.profiler.trace``)
holds the host's rounds on the device's clock.  It is a sink of its own:
without a tracer the spans annotate and nothing else is recorded.
"""

from __future__ import annotations

from typing import Optional

from jax.profiler import TraceAnnotation

from .metrics import MetricsRegistry
from .trace import Tracer

__all__ = ["Obs", "NULL_SPAN"]


class _NullSpan:
    """Shared do-nothing context manager (the tracing-off fast path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Obs:
    """Observability handle: a metrics registry plus an optional tracer.

    Parameters
    ----------
    metrics: registry to record into (default: a fresh one — callers that
        want engine + scheduler + bench in one registry pass it explicitly).
    tracer: a :class:`Tracer` to record the span timeline into, or None
        (the default) for metrics-only operation.
    xla_annotations: make every span a ``jax.profiler.TraceAnnotation``
        too, so XLA device profiles interleave (with or without a tracer).
    """

    def __init__(self, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 xla_annotations: bool = False):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer
        self.xla_annotations = xla_annotations

    @property
    def tracing(self) -> bool:
        return self.tracer is not None

    # ------------------------------------------------------------------
    # span/event hooks (no-ops without a tracer; spans also annotate the
    # profiler with ``xla_annotations``)
    # ------------------------------------------------------------------

    def span(self, name: str, **args):
        """Span on the serve-loop track, and a profiler annotation with
        ``xla_annotations``."""
        if self.tracer is None:
            return TraceAnnotation(name) if self.xla_annotations else NULL_SPAN
        ann = TraceAnnotation(name) if self.xla_annotations else None
        return self.tracer.span(name, ann=ann, **args)

    def event(self, name: str, **args):
        if self.tracer is not None:
            self.tracer.instant(name, **args)

    def counter(self, name: str, value: float):
        if self.tracer is not None:
            self.tracer.counter(name, value)

    def request_begin(self, rid: int, **args):
        if self.tracer is not None:
            self.tracer.request_begin(rid, **args)

    def request_event(self, rid: int, name: str, **args):
        if self.tracer is not None:
            self.tracer.request_event(rid, name, **args)

    def request_end(self, rid: int, **args):
        if self.tracer is not None:
            self.tracer.request_end(rid, **args)

    def export(self, path: str) -> int:
        """Export the trace (0 events when tracing is off)."""
        return self.tracer.export(path) if self.tracer is not None else 0
