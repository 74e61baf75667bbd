"""Unified observability: span tracer + metrics registry + surfacing.

Reference: the stats chain ``operator/OperatorStats.java`` →
Driver → Task → Stage → ``execution/QueryStats.java``, surfaced over JMX
and event listeners. Our port keeps the same three altitudes with
TPU-era span names (program trace/lower/compile, device→host pulls,
exchange transfers) instead of per-operator CPU counters:

- :mod:`trino_tpu.obs.trace` — lightweight structured spans. Trace id =
  query id; spans parent across processes via the ``X-Trino-Trace``
  HTTP header. Emission is a no-op unless a sink is registered.
- :mod:`trino_tpu.obs.metrics` — process-global counters, gauges and
  fixed-bucket histograms (no external deps), rendered in Prometheus
  text format at ``GET /v1/metrics`` and embedded as a JSON snapshot by
  ``scripts/chaos_smoke.py``.
"""

from trino_tpu.obs.metrics import get_registry
from trino_tpu.obs.trace import InMemorySpanSink, get_tracer

__all__ = ["get_registry", "get_tracer", "InMemorySpanSink"]
