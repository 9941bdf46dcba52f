"""`repro.obs` — zero-sync serve observability.

Four pieces, threaded through the serving stack at host-side seams only:

* :mod:`repro.obs.metrics` — a typed metrics registry (counters, gauges,
  series, fixed-bucket log2 histograms) that backs the scheduler's ``stats``
  and produces the exact summary dict ``BENCH_serving.json`` records.
* :mod:`repro.obs.trace` — a span/event recorder exporting Chrome/Perfetto
  ``trace_event`` JSON: round anatomy spans on the scheduler track plus one
  lifecycle track per request.
* :mod:`repro.obs.recorder` — the ``Obs`` facade the engine/scheduler/bench
  accept (``obs=...``), with a free no-op path when tracing is off; with
  ``xla_annotations=True`` every serve-loop span is also a
  ``jax.profiler.TraceAnnotation`` (with or without a tracer).
* :mod:`repro.obs.scopes` — the fixed names a profile is read by: the
  ``jax.named_scope`` phases and parts of the serve programs on the device,
  and the serve loop's span names on the host.

The hard contract (tested in tests/test_obs.py): with tracing ON, served
tokens stay byte-identical and ``dispatches``/``host_syncs`` do not move —
observability reads host-side values the serve loop already holds and never
adds a device sync; with tracing OFF the recorder costs one predictable
branch per seam.
"""

from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    LogHistogram,
    MetricsRegistry,
    Series,
    StatsView,
)
from .recorder import NULL_SPAN, Obs  # noqa: F401
from .trace import Tracer, validate_trace  # noqa: F401
