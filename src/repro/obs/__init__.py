"""repro.obs — repo-wide observability: spans, metrics, recall probes.

The subsystem the rest of the library reports into, and the one place
operators read from:

* :mod:`repro.obs.trace` — hierarchical spans (wall time), bounded
  buffer, Chrome-trace export, mirrored into ``jax.profiler`` captures.
  Off by default; ``enable()``.
* :mod:`repro.obs.registry` — process-global counters / gauges /
  exact-percentile latency recorders; JSON snapshot + Prometheus text.
* :mod:`repro.obs.dispatch` — per-site dispatch counters and the
  jax.monitoring recompile detector (the pow2-bucket "never recompiles
  in steady state" invariant as a live gauge).
* :mod:`repro.obs.recall` — sampled online recall@k vs. an exact
  brute-force shadow, scored off the query path.
* :mod:`repro.obs.http` — ``/metrics`` (Prometheus), ``/metrics.json``,
  ``/trace`` endpoints on a stdlib HTTP server.

Span taxonomy and the metric catalog are documented in
docs/OBSERVABILITY.md.
"""

from .dispatch import (
    accounting_delta,
    accounting_snapshot,
    compiles_total,
    dispatch_counts,
    dispatch_scope,
    install_compile_listener,
    recompile_counts,
)
from .http import MetricsServer, serve_metrics
from .recall import (
    RecallProbe,
    RecallProbeConfig,
    exact_topk,
    live_points,
    recall_at_k,
)
from .registry import (
    Counter,
    Gauge,
    LatencyRecorder,
    MetricsRegistry,
    default_registry,
    percentile_label,
    percentiles,
)
from .trace import Span, Tracer, default_tracer, disable, enable, span

__all__ = [
    "accounting_delta", "accounting_snapshot", "compiles_total",
    "dispatch_counts", "dispatch_scope",
    "install_compile_listener", "recompile_counts",
    "MetricsServer", "serve_metrics",
    "RecallProbe", "RecallProbeConfig", "exact_topk", "live_points",
    "recall_at_k",
    "Counter", "Gauge", "LatencyRecorder", "MetricsRegistry",
    "default_registry", "percentile_label", "percentiles",
    "Span", "Tracer", "default_tracer", "disable", "enable", "span",
]
