"""repro.obs — observability: device-timeline tracing + typed metrics.

Three dependency-light modules that sit under every layer of the stack
without cycles.  Only ``trace`` uses jax, and imports ``jax.profiler`` on
its first span, so ``metrics`` and ``report`` import without jax:

- ``trace``   — span-based :class:`Tracer` reconstructing the simulated
  device timeline (one virtual lane per die / channel / host link, start
  offsets derived from the ledger's schedule-step model so the longest lane
  equals ``makespan_us()`` by construction) plus host wall-clock spans, with
  Chrome trace-event (`chrome://tracing` / Perfetto) JSON export; and
  :func:`traced`, the one instrumentation point, which also puts every host
  span on the JAX profiler's clock as ``repro.<category>``.
- ``metrics`` — :class:`Counter` / :class:`Gauge` / :class:`Histogram` and
  the :class:`MetricsRegistry` backing ``ComputeSession`` / cache ``stats()``.
- ``report``  — human-readable text timeline (per-category, per-lane,
  per-wave tables).

The modelled NAND timeline is on with ``ComputeSession(trace=True)``;
export it with ``session.trace.export("out.json")`` or print
``session.trace.report()``.  The ``repro.*`` host spans need no flag: any
``jax.profiler.start_trace`` or profiler-server capture holds them.
"""
from repro.obs.metrics import Counter, Gauge, Histogram, Metric, MetricsRegistry
from repro.obs.report import timeline_report
from repro.obs.trace import Span, Tracer, traced

__all__ = ["Counter", "Gauge", "Histogram", "Metric", "MetricsRegistry",
           "Span", "Tracer", "timeline_report", "traced"]
