"""Serve/train observability subsystem (docs/observability.md).

Three layers, each usable on its own:

* :mod:`repro.obs.trace` — spans as ``jax.profiler`` annotations, on
  the device trace's clock under a profiler session, and an optional
  in-memory chrome-trace recorder (Perfetto-loadable JSON);
* :mod:`repro.obs.metrics` — typed Counter/Gauge/Histogram instruments in
  a :class:`~repro.obs.metrics.MetricsRegistry` (bounded memory,
  p50/p95/p99 from fixed buckets);
* :mod:`repro.obs.replay` — a :class:`~repro.obs.replay.CostModel` fitted
  from recorded traces plus a replay simulator that re-runs the *real*
  scheduler stack against simulated step costs (imported lazily — it
  pulls in the serve stack; ``import repro.obs.replay`` explicitly).

Only the layers that import nothing of the program are imported eagerly,
so any module can import ``repro.obs.trace`` without cycles.
"""
from repro.obs import metrics, trace  # noqa: F401
